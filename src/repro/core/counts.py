"""Linearithmic RankSVM frequency computation — the paper's contribution, TPU-native.

The paper sweeps examples in sorted-p order while maintaining a red-black
order-statistics tree over the y-values inside the moving margin frontier
(Algorithm 3). A pointer-based, sequentially-updated tree has no TPU analogue,
but the *schedule* of the sweep is fully known after one sort:

  * elements are inserted in sorted-p order, and
  * query i fires when the frontier holds exactly
        L_i = |{k : p_k < p_i + 1}|
    elements (L is monotone in sorted-p order).

So the dynamic tree can be replaced by a *static, implicit order-statistics
structure* — a merge-sort tree — built with parallel sorts and queried with
vectorized branchless binary searches:

  level b stores y (in p-order) sorted inside aligned blocks of 2^b; the prefix
  [0, L_i) decomposes into one aligned block per set bit of L_i, and the rank
  query "count y_k > y_i in the prefix" becomes <= log2(m)+1 independent
  binary searches per element. Everything is dense, regular, and batched: the
  TPU-native equivalent of the red-black tree.

Work: O(m log^2 m); depth: O(log m); identical counts to the O(m^2) oracle
(including the paper's exact strict/non-strict tie semantics).

The sharded oracle (core.distributed) searches that tree, `_half_counts`,
and obtains d from c by the reflection d(p, y) = c(-p, -y), which is exact
in floating point (negation is exact and round-to-nearest is odd-symmetric,
so the margin comparisons match the oracle's bit-for-bit). The weighted
counts search it too.

The binary searches are gathers, the one primitive a TPU does worst. So
the engine everything else runs, `counts_fused`, answers all queries
offline instead: c and d are dominance counts over points and queries
placed in one p-order, and the same merge-sort tree is built bottom-up
over y-rank with the queries riding through its level sorts. It is sorts,
cumsums, slices and concatenations with no gather, scatter or search, for
the same work bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _next_pow2(m: int) -> int:
    return 1 if m <= 1 else 1 << (m - 1).bit_length()


def _count_cmp_in_block(flat: jnp.ndarray, base: jnp.ndarray, t: jnp.ndarray,
                        block: int, strict: bool) -> jnp.ndarray:
    """Vectorized branchless binary search.

    For each query q: count of elements < t[q] (strict) or <= t[q] inside the
    sorted block flat[base[q] : base[q] + block]. `block` is a power of two.
    Indices are clamped; callers mask out-of-range queries themselves.
    """
    cmp = jnp.less if strict else jnp.less_equal
    mmax = flat.shape[0] - 1
    i = jnp.zeros_like(base)
    step = block // 2
    while step >= 1:
        idx = jnp.minimum(base + i + step - 1, mmax)
        i = i + jnp.where(cmp(jnp.take(flat, idx), t), step, 0)
        step //= 2
    idx = jnp.minimum(base + i, mmax)
    return i + cmp(jnp.take(flat, idx), t).astype(i.dtype)


def _count_le_in_block(flat: jnp.ndarray, base: jnp.ndarray, t: jnp.ndarray,
                       block: int) -> jnp.ndarray:
    return _count_cmp_in_block(flat, base, t, block, strict=False)


def _tree_levels(y_pad: jnp.ndarray) -> dict:
    """Merge-sort-tree levels: level b holds y_pad sorted inside aligned
    blocks of 2^b, flattened. Level 0 (the raw array) is y_pad itself and is
    not stored. Built once, queryable many times (`_prefix_query`)."""
    mpad = y_pad.shape[0]
    nlev = mpad.bit_length() - 1
    levels = {}
    for b in range(1, nlev + 1):
        block = 1 << b
        if block == mpad:
            levels[b] = jnp.sort(y_pad)
        else:
            levels[b] = jnp.sort(y_pad.reshape(mpad // block, block),
                                 axis=1).reshape(-1)
    return levels


def _tree_levels_weighted(y_pad: jnp.ndarray, v_pad: jnp.ndarray):
    """`_tree_levels` plus per-level inclusive prefix sums of the weights
    in each block's sorted-y order — the ONE extra weighted prefix-sum the
    position-weighted hinge needs (DESIGN.md §12): a weighted rank query
    becomes `block total - prefix sum at the binary-search position`, so
    the query structure of `_prefix_query` carries over unchanged.

    The sorted y values are identical to `_tree_levels` (same per-block
    sort keys), so unweighted queries still run against these levels.
    """
    mpad = y_pad.shape[0]
    nlev = mpad.bit_length() - 1
    levels, wsums = {}, {}
    for b in range(1, nlev + 1):
        block = 1 << b
        y2 = y_pad.reshape(mpad // block, block)
        order = jnp.argsort(y2, axis=1)
        v2 = jnp.take_along_axis(v_pad.reshape(mpad // block, block),
                                 order, axis=1)
        levels[b] = jnp.take_along_axis(y2, order, axis=1).reshape(-1)
        wsums[b] = jnp.cumsum(v2, axis=1).reshape(-1)
    return levels, wsums


def _prefix_weighted_gt(levels: dict, wsums: dict, y_pad: jnp.ndarray,
                        v_pad: jnp.ndarray, prefix_len: jnp.ndarray,
                        thresholds: jnp.ndarray) -> jnp.ndarray:
    """Weighted 'gt' prefix query: for each query i,
        sum of v_seq[k] over {k < prefix_len[i] : y_seq[k] > thresholds[i]}
    against levels/wsums from `_tree_levels_weighted`. Same aligned-block
    decomposition as `_prefix_query`; each block contributes its total
    weight minus the weight prefix at the `count <= t` search position."""
    mpad = y_pad.shape[0]
    nlev = mpad.bit_length() - 1
    mmax = mpad - 1
    total = jnp.zeros(thresholds.shape, jnp.float32)
    for b in range(nlev + 1):
        block = 1 << b
        bit = (prefix_len >> b) & 1
        base = (prefix_len >> (b + 1)) << (b + 1)   # bits <= b cleared
        if block == 1:
            idx = jnp.minimum(base, mmax)
            w = jnp.where(jnp.take(y_pad, idx) > thresholds,
                          jnp.take(v_pad, idx), 0.0)
        else:
            pos = _count_le_in_block(levels[b], base, thresholds, block)
            tot = jnp.take(wsums[b], jnp.minimum(base + block - 1, mmax))
            lo = jnp.take(wsums[b],
                          jnp.clip(base + pos - 1, 0, mmax))
            w = tot - jnp.where(pos > 0, lo, 0.0)
        total = total + jnp.where(bit == 1, w, 0.0)
    return total


def _prefix_weighted_greater(y_seq: jnp.ndarray, v_seq: jnp.ndarray,
                             prefix_len: jnp.ndarray,
                             thresholds: jnp.ndarray) -> jnp.ndarray:
    """For each query i: sum of v_seq[k] over
    {k < prefix_len[i] : y_seq[k] > thresholds[i]} — the weighted analogue
    of `_prefix_count_greater` (used by the position-weighted ranking
    metric, core.rank_loss.position_weighted_error)."""
    m = y_seq.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.float32)
    mpad = _next_pow2(m)
    y_pad = jnp.pad(y_seq, (0, mpad - m), constant_values=jnp.inf)
    v_pad = jnp.pad(v_seq.astype(jnp.float32), (0, mpad - m))
    levels, wsums = _tree_levels_weighted(y_pad, v_pad)
    return _prefix_weighted_gt(levels, wsums, y_pad, v_pad, prefix_len,
                               thresholds)


def _prefix_query(levels: dict, y_pad: jnp.ndarray, prefix_len: jnp.ndarray,
                  thresholds: jnp.ndarray, mode: str,
                  constrain=None) -> jnp.ndarray:
    """For each query i over prebuilt levels:
        mode 'gt': |{k < prefix_len[i] : y_seq[k] > thresholds[i]}|
        mode 'lt': |{k < prefix_len[i] : y_seq[k] < thresholds[i]}|

    `constrain` (optional) is applied to every query-indexed array — the
    distributed oracle passes a with_sharding_constraint that shards the
    QUERY side over the mesh while the tree levels stay replicated
    (core.distributed; the tree is 4 MB, the query work is the O(m log^2 m)
    term)."""
    mpad = y_pad.shape[0]
    nlev = mpad.bit_length() - 1
    cns = constrain or (lambda x: x)
    prefix_len = cns(prefix_len)
    thresholds = cns(thresholds)
    total = cns(jnp.zeros_like(prefix_len))
    for b in range(nlev + 1):
        block = 1 << b
        bit = (prefix_len >> b) & 1
        base = cns((prefix_len >> (b + 1)) << (b + 1))  # bits <= b cleared
        if block == 1:
            v = jnp.take(y_pad, jnp.minimum(base, mpad - 1))
            cnt = ((v > thresholds) if mode == 'gt'
                   else (v < thresholds)).astype(jnp.int32)
        elif mode == 'gt':
            cnt = block - _count_le_in_block(levels[b], base, thresholds,
                                             block)
        else:
            cnt = _count_cmp_in_block(levels[b], base, thresholds, block,
                                      strict=True)
        total = cns(total + jnp.where(bit == 1, cnt, 0))
    return total


def _prefix_count_greater(y_seq: jnp.ndarray, prefix_len: jnp.ndarray,
                          thresholds: jnp.ndarray,
                          constrain=None) -> jnp.ndarray:
    """For each query i: |{k < prefix_len[i] : y_seq[k] > thresholds[i]}|."""
    m = y_seq.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.int32)
    mpad = _next_pow2(m)
    # Padding value is irrelevant: prefix_len <= m, and every aligned block
    # used by the decomposition lies entirely inside [0, prefix_len).
    y_pad = jnp.pad(y_seq, (0, mpad - m), constant_values=jnp.inf)
    return _prefix_query(_tree_levels(y_pad), y_pad, prefix_len, thresholds,
                         'gt', constrain=constrain)


def _half_counts(p: jnp.ndarray, y: jnp.ndarray,
                 constrain=None) -> jnp.ndarray:
    """c_i = |{j : y_j > y_i  and  p_j < p_i + 1}| in O(m log^2 m)."""
    m = p.shape[0]
    order = jnp.argsort(p)
    ps = jnp.take(p, order)
    ys = jnp.take(y, order)
    # Frontier: the tree inserts j while p_j < p_i + 1 (strict) -> in sorted-p
    # order the inserted set is exactly the prefix [0, L_i). The queries
    # (ps + 1) are per-example -> constrained so the binary search shards.
    q = ps + jnp.asarray(1.0, ps.dtype)
    if constrain is not None:
        q = constrain(q)
    frontier = jnp.searchsorted(ps, q, side='left').astype(jnp.int32)
    c_sorted = _prefix_count_greater(ys, frontier, ys, constrain=constrain)
    return jnp.zeros((m,), jnp.int32).at[order].set(c_sorted)


# Item kinds of `counts_fused`'s offline dominance count. On equal sort
# keys the ordering sort puts a c-query before a point and a d-query after
# it. Its uint32 level keys hold at most `_DOMINANCE_MAX_ITEMS` items.
_C_QUERY, _POINT, _D_QUERY, _PAD = 0, 1, 2, 3
_DOMINANCE_MAX_ITEMS = 1 << 29
# A TPU tile is 8 sublanes by 128 lanes. A row-major (blocks, block) array
# pads each row of a block under 128 items to 128 lanes, and a sort over
# fewer than 8 rows runs on part-filled tiles (the top three levels took
# 60 of 125 ms at 2^22 items on a v5e).
_TILE_ROWS, _LANES = 8, 128


def _bit_reverse(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """x with its low `bits` bits in reverse order."""
    r = jnp.zeros_like(x)
    for k in range(bits):
        r = r | ((x >> k) & 1) << (bits - 1 - k)
    return r


@jax.jit
def counts_fused(p: jnp.ndarray, y: jnp.ndarray):
    """(c, d) as one offline dominance count: a merge-sort tree built
    bottom-up with the queries riding through it — the oracle-layer fast
    path (core.oracle), bit-identical to `ref.counts_ref`.

    Both frequency vectors count points that one item dominates:

        c_i = |{k : y_k > y_i  and  p_k < p_i + 1}|
        d_i = |{k : y_k < y_i  and  p_k > p_i - 1}|

    Each example i gives three items: a c-query at key p_i + 1, a point at
    p_i and a d-query at p_i - 1, all carrying y_i. One sort by (key, kind)
    with kind c-query < point < d-query puts before each c-query exactly
    the points with p_k < p_i + 1, and after each d-query exactly those
    with p_k > p_i - 1: both compare against the same rounded f32 values
    as the reference, so tie semantics hold bit for bit. A second sort
    ranks the items by (y, kind) with d-query < point < c-query on equal
    y, so "y_k > y_i" becomes "higher rank" for a c-query and "y_k < y_i"
    "lower rank" for a d-query ('sort').

    Level b merges pairs of blocks of 2^b ranks into blocks of 2^(b+1)
    and re-sorts each by p-order position ('tree'); the left half holds
    the lower ranks. In the merged block a left c-query adds the
    right-half points before it and a right d-query the left-half points
    after it, read off cumsums along the block ('query'). Every (point,
    query) pair splits at exactly one level, so the per-item counts, which
    ride through the sorts as a second operand, end as (c, d). After the
    last level the items stand in p-order; one sort by item id puts the
    counts in example order ('unsort').

    Of R blocks, block r merges with block r + R/2: the items start in
    bit-reversed rank order (slot j holds rank bitrev(j), one more sort),
    which makes the two merged blocks adjacent rank ranges, and each
    level's input is the previous level's two halves side by side, with
    no reshape through a flat array. Blocks of up to 128 items are the
    columns of a (block, blocks) array and larger ones its rows, so no
    level pads a block to the 128 lanes of a tile. The level key packs
    the p-order position, the kind and the side bit into a uint32.

    O(m log^2 m) work, O(log m) depth, and no gather, scatter or binary
    search: only sorts, cumsums, slices and concatenations.
    """
    p = p.astype(jnp.float32) if p.dtype == jnp.float64 else p
    m = p.shape[0]
    if m == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z
    n = _next_pow2(3 * m)
    if n > _DOMINANCE_MAX_ITEMS:
        raise ValueError(f'counts_fused holds at most '
                         f'{_DOMINANCE_MAX_ITEMS // 3} examples; got {m}')
    nb = n.bit_length() - 1
    pos = jax.lax.iota(jnp.int32, n)

    with jax.named_scope('sort'):
        one = jnp.asarray(1.0, p.dtype)
        pad = n - 3 * m
        key = jnp.concatenate([p + one, p, p - one,
                               jnp.full((pad,), jnp.inf, p.dtype)])
        ys = jnp.concatenate([y, y, y, jnp.zeros((pad,), y.dtype)])
        # item id: kind * m + example
        _, item, ys = jax.lax.sort((key, pos, ys), num_keys=2,
                                   is_stable=False)
        tie = 2 - jnp.minimum(item // m, _PAD)      # pad < d < point < c
        _, rank_key = jax.lax.sort((ys, (tie << nb) | pos), num_keys=2,
                                   is_stable=False)
        # level key, high to low: p-order position, kind, side bit
        kind = (2 - (rank_key >> nb)).astype(jnp.uint32)
        key = (rank_key & (n - 1)).astype(jnp.uint32) << 3 | kind << 1
        _, key = jax.lax.sort((_bit_reverse(pos, nb), key), num_keys=1,
                              is_stable=False)

    # blocks along axis `ax`: columns (ax = 0) while they are small
    key, acc, ax = key.reshape(1, n), jnp.zeros((1, n), jnp.int32), 0
    for b in range(nb):
        rows, block = n >> (b + 1), 2 << b
        with jax.named_scope('tree'):
            if ax == 0 and block > _LANES:
                key, acc, ax = key.T, acc.T, 1
            # block r and block r + rows side by side, the second's
            # items marked right
            lo, hi = jnp.split(key, 2, axis=1 - ax)
            key = jnp.concatenate([lo, hi | 1], axis=ax)
            acc = jnp.concatenate(jnp.split(acc, 2, axis=1 - ax), axis=ax)
            row_bits = nb - b - 1
            if ax == 1 and rows < _TILE_ROWS and nb + 3 + row_bits <= 32:
                # one flat sort, the row index leading the key
                low = jnp.uint32((1 << (32 - row_bits)) - 1)
                row = (pos >> (b + 1)).astype(jnp.uint32) * (low + 1)
                flat, acc = jax.lax.sort((key.reshape(-1) | row,
                                          acc.reshape(-1)),
                                         num_keys=1, is_stable=False)
                key = (flat & low).reshape(rows, block)
                acc = acc.reshape(rows, block)
            else:
                key, acc = jax.lax.sort((key, acc), dimension=ax,
                                        num_keys=1, is_stable=False)
        with jax.named_scope('query'):
            kind = (key >> 1) & 3
            right = (key & 1) != 0
            point = kind == _POINT
            left_pts = jnp.cumsum(point & ~right, axis=ax, dtype=jnp.int32)
            right_pts = jnp.cumsum(point & right, axis=ax, dtype=jnp.int32)
            total = left_pts[-1:] if ax == 0 else left_pts[:, -1:]
            acc = acc + (jnp.where((kind == _C_QUERY) & ~right, right_pts, 0)
                         + jnp.where((kind == _D_QUERY) & right,
                                     total - left_pts, 0))
            key = key & ~jnp.uint32(1)

    with jax.named_scope('unsort'):
        _, acc = jax.lax.sort((item, acc.reshape(-1)), num_keys=1,
                              is_stable=False)
        return acc[:m], acc[2 * m:3 * m]


@jax.jit
def counts_grouped_fused(p: jnp.ndarray, y: jnp.ndarray, g: jnp.ndarray):
    """(c, d) restricted to within-group pairs, still one linearithmic pass
    of `counts_fused` over offset keys (`_group_offsets`).

    Precision note: group offsets consume dynamic range; with float32 scores
    keep |groups| * (range(p)+range(y)) below ~1e4 so that one ulp at the
    largest offset key stays well under the hinge margin of 1. The reward-model
    batch use-case (<= a few hundred groups, |p| ~ O(10)) is far inside this.
    """
    pg, yg = _group_offsets(p, y, g)
    return counts_fused(pg, yg)


@jax.jit
def counts_weighted_fused(p: jnp.ndarray, y: jnp.ndarray, v: jnp.ndarray):
    """(c~, d) for the position-weighted hinge: ONE sort, ONE weighted tree.

        c~_i = sum of v_j over {j : y_j > y_i  and  p_j < p_i + 1}  (float32)
        d_i  = |{j : y_j < y_i  and  p_j > p_i - 1}|                (int32)

    A weighted pair (i, j) (y_i < y_j inside the margin) carries the weight
    v_j of its higher-utility side, so only the c-side query is weighted —
    the d-side contribution of example j is its OWN weight v_j times the
    ordinary count d_j, applied by the caller (core.oracle, loss='poshinge').
    The weighted levels carry the searching tree's sorted-y blocks
    (`_tree_levels`), so d comes from c's tree by the exact complement
        d_i = |{k : y_k < y_i}| - |{k : y_k < y_i  and  p_k <= p_i - 1}|
    (`p_k <= p_i - 1` is the float complement of the reference's
    `p_k > p_i - 1`, so tie semantics hold bit-for-bit); c~ replaces the
    block counts with block weight sums (`_prefix_weighted_gt`). Work
    stays O(m log^2 m): one cumsum per level on top of the sorts.
    """
    p = p.astype(jnp.float32) if p.dtype == jnp.float64 else p
    m = p.shape[0]
    if m == 0:
        return jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32)
    order = jnp.argsort(p)
    ps = jnp.take(p, order)
    ys = jnp.take(y, order)
    vs = jnp.take(v.astype(jnp.float32), order)
    mpad = _next_pow2(m)
    y_pad = jnp.pad(ys, (0, mpad - m), constant_values=jnp.inf)
    v_pad = jnp.pad(vs, (0, mpad - m))
    levels, wsums = _tree_levels_weighted(y_pad, v_pad)

    one = jnp.asarray(1.0, ps.dtype)
    frontier = jnp.searchsorted(ps, ps + one, side='left').astype(jnp.int32)
    cw_sorted = _prefix_weighted_gt(levels, wsums, y_pad, v_pad, frontier,
                                    ys)
    inner = jnp.searchsorted(ps, ps - one, side='right').astype(jnp.int32)
    lt_inner = _prefix_query(levels, y_pad, inner, ys, 'lt')
    glt = jnp.searchsorted(jnp.sort(y), ys, side='left').astype(jnp.int32)
    d_sorted = glt - lt_inner

    zi = jnp.zeros((m,), jnp.int32)
    return (jnp.zeros((m,), jnp.float32).at[order].set(cw_sorted),
            zi.at[order].set(d_sorted))


@jax.jit
def counts_weighted_grouped_fused(p: jnp.ndarray, y: jnp.ndarray,
                                  g: jnp.ndarray, v: jnp.ndarray):
    """Grouped (c~, d) via the key-offset trick: cross-group elements are
    pushed outside the margin/preference conditions (`_group_offsets`), so
    their weights contribute exactly zero to every c~ query; the weights
    themselves ride along unchanged."""
    pg, yg = _group_offsets(p, y, g)
    return counts_weighted_fused(pg, yg, v)


@functools.partial(jax.jit, static_argnames=('block',))
def counts_blocked_weighted(p, y, v, block: int = 2048):
    """O(m^2) weighted pairwise (c~, d) with O(m*block) memory — the
    blocked-engine counterpart of `counts_weighted_fused` (differential
    anchor + large-m fallback, same role `counts_blocked_host` plays for
    the uniform hinge)."""
    m = p.shape[0]
    nblk = -(-m // block)
    pp = jnp.pad(p, (0, nblk * block - m))
    yp = jnp.pad(y, (0, nblk * block - m), constant_values=jnp.nan)
    vp = jnp.pad(v.astype(jnp.float32), (0, nblk * block - m))

    def body(carry, blk):
        pj, yj, vj = blk  # (block,)
        cw = jnp.sum(jnp.where((yj[None, :] > y[:, None])
                               & (pj[None, :] < p[:, None] + 1.0),
                               vj[None, :], 0.0), axis=1)
        d = jnp.sum((yj[None, :] < y[:, None])
                    & (pj[None, :] > p[:, None] - 1.0), axis=1)
        return carry, (cw, d.astype(jnp.int32))

    _, (cs, ds) = jax.lax.scan(
        body, None, (pp.reshape(nblk, block), yp.reshape(nblk, block),
                     vp.reshape(nblk, block)))
    return jnp.sum(cs, axis=0), jnp.sum(ds, axis=0)


ENGINES = ('tree', 'blocked', 'pallas', 'auto')


def _validate_engine(engine: str) -> None:
    """Reject typo'd engine names before any work (or any late import)
    happens: `counts_dispatch` runs at trace time inside the oracles'
    jitted steps, and an error surfacing from a half-built trace is far
    less actionable than one thrown at the dispatch boundary."""
    if engine not in ENGINES:
        raise ValueError(f'unknown counting engine {engine!r}; '
                         f'expected one of {ENGINES}')


def counts_dispatch(p, y, g, engine: str = 'tree', block: int = 2048,
                    v=None):
    """Trace-time dispatch over counting engines — THE counting core every
    oracle shares (fused `_FusedOracle` and chunked `StreamingOracle`
    alike; previously forked inside the oracle layer).

    g is None for ungrouped counting; grouped counting applies the
    key-offset trick (`_group_offsets`) before the chosen engine runs.
    engine: 'tree' (merge-sort tree, the paper), 'blocked' (O(m^2)
    pairwise, O(m*block) memory), 'pallas' (`kernels.rank_counts`: both
    frequency vectors in one fused tiled on-chip pass, DESIGN.md §8),
    'auto' (`kernels.pairwise_rank.counts_auto`: measured tiering —
    Pallas pairwise for small m on TPU, Pallas rank-counts above it,
    tree lowering elsewhere).

    v (optional, per-example float weights) switches to WEIGHTED counting
    for the position-weighted hinge: returns (c~, d) with c~ the weighted
    higher-utility-side sums (`counts_weighted_fused`) instead of the
    integer c. The 'tree' engine runs the weighted tree, 'blocked' the
    weighted pairwise pass; the Pallas kernels carry no weighted variant,
    so 'pallas' and 'auto' fall back to the weighted tree (DESIGN.md §12
    — the honest dispatch: on CPU 'auto' resolves to the tree anyway, and
    a silent unweighted kernel would compute the wrong objective).

    engine and block are validated up front: `engine` against `ENGINES`
    and, for the one engine that consumes it, `block` through the same
    `_validate_block_rows` gate as every other block-sized knob — a
    typo'd engine or a fractional/non-positive block fails here with an
    actionable message instead of deep inside a trace.
    """
    _validate_engine(engine)
    if engine == 'blocked':
        # function-local import: repro.data pulls heavier deps and the
        # core counting module stays importable without it
        from ..data.rowblocks import _validate_block_rows
        block = _validate_block_rows(block, 'counts_dispatch block')
    if v is not None:
        if engine == 'blocked':
            if g is not None:
                p, y = _group_offsets(p, y, g)
            return counts_blocked_weighted(p, y, v, block=block)
        # 'tree', and the documented 'pallas'/'auto' weighted fallback
        if g is None:
            return counts_weighted_fused(p, y, v)
        return counts_weighted_grouped_fused(p, y, g, v)
    if engine == 'tree':
        if g is None:
            return counts_fused(p, y)
        return counts_grouped_fused(p, y, g)
    if g is not None:
        p, y = _group_offsets(p, y, g)
    if engine == 'auto':
        # late import + attribute lookup so the kernel-vs-tree switch stays
        # patchable (tests) and the pallas import stays off the core path
        from repro.kernels.pairwise_rank import ops as _pr_ops
        return _pr_ops.counts_auto(p, y)
    if engine == 'pallas':
        from repro.kernels.rank_counts import ops as _rc_ops
        return _rc_ops.rank_counts(p, y)
    return counts_blocked_host(p, y, block=block)


@jax.jit
def num_pairs(y: jnp.ndarray) -> jnp.ndarray:
    """N = |{(i, j) : y_i < y_j}| in O(m log m), returned as float32.

    float32 because jax without x64 lacks int64 and m^2 overflows int32; the
    relative error (<= 2^-24) only perturbs the loss normalization. Exact
    host-side computation is available via `num_pairs_host`.
    """
    m = y.shape[0]
    ys = jnp.sort(y)
    eq = (jnp.searchsorted(ys, y, side='right')
          - jnp.searchsorted(ys, y, side='left')).astype(jnp.float32)
    mm = jnp.asarray(float(m) * float(m), jnp.float32)
    return (mm - jnp.sum(eq)) * 0.5


def num_pairs_host(y) -> int:
    """Exact N on host (python ints)."""
    y = np.asarray(y)
    m = int(y.shape[0])
    _, cnts = np.unique(y, return_counts=True)
    ties = int(np.sum(cnts.astype(np.int64) ** 2))
    return (m * m - ties) // 2


def _group_offsets(p, y, g):
    """Per-group key offsets making ONE global tree pass compute per-group
    counts exactly.

    With dp > range(p)+2 and dy > range(y), set p~ = p + g*dp, y~ = y + g*dy.
    For a cross-group pair with g_j > g_i: p~_j >= p~_i + 2 > p~_i + 1 so the
    margin condition of c fails; for g_j < g_i: y~_j < y~_i so the preference
    condition fails. Symmetrically for d. Hence cross-group pairs contribute
    nothing and within-group comparisons are unchanged (offsets cancel).
    """
    gf = g.astype(p.dtype)
    dp = (jnp.max(p) - jnp.min(p)) + jnp.asarray(2.5, p.dtype)
    dy = (jnp.max(y) - jnp.min(y)).astype(p.dtype) + jnp.asarray(1.0, p.dtype)
    return p + gf * dp, y.astype(p.dtype) + gf * dy


@jax.jit
def num_pairs_grouped(y: jnp.ndarray, g: jnp.ndarray) -> jnp.ndarray:
    """N restricted to within-group pairs, as float32 (see num_pairs)."""
    m = y.shape[0]
    yf = y.astype(jnp.float32)
    dy = (jnp.max(yf) - jnp.min(yf)) + 1.0
    yg = yf + g.astype(jnp.float32) * dy
    # Total ordered pairs under offset keys = within-group y_i<y_j pairs plus
    # ALL cross-group pairs (offsets force a strict order across groups).
    n_off = num_pairs(yg)
    gs = jnp.sort(g.astype(jnp.float32))
    eq = (jnp.searchsorted(gs, g.astype(jnp.float32), side='right')
          - jnp.searchsorted(gs, g.astype(jnp.float32), side='left'))
    cross = (float(m) * float(m) - jnp.sum(eq.astype(jnp.float32))) * 0.5
    return n_off - cross


@functools.partial(jax.jit, static_argnames=('block',))
def counts_blocked_host(p, y, block: int = 2048):
    """O(m^2) pairwise counts with O(m*block) memory (PairRSVM baseline).

    Used by the CPU benchmark path for large m where the full m x m mask of
    ref.counts_ref would not fit in memory.
    """
    m = p.shape[0]
    nblk = -(-m // block)
    pp = jnp.pad(p, (0, nblk * block - m))
    yp = jnp.pad(y, (0, nblk * block - m), constant_values=jnp.nan)

    def body(carry, blk):
        pj, yj = blk  # (block,)
        c = jnp.sum((yj[None, :] > y[:, None])
                    & (pj[None, :] < p[:, None] + 1.0), axis=1)
        d = jnp.sum((yj[None, :] < y[:, None])
                    & (pj[None, :] > p[:, None] - 1.0), axis=1)
        return carry, (c.astype(jnp.int32), d.astype(jnp.int32))

    _, (cs, ds) = jax.lax.scan(
        body, None, (pp.reshape(nblk, block), yp.reshape(nblk, block)))
    return jnp.sum(cs, axis=0), jnp.sum(ds, axis=0)
