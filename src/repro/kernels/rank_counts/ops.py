"""Jit'd public wrappers around the fused rank-counting Pallas kernel.

`rank_counts` is the `counts_dispatch(engine='pallas')` entry: it owns
the sort, the compact y-rank compression, the tile padding, the
histogram/band precomputation, the level-capacity guard (an in-trace
fallback to the merge-sort tree keeps results exact for ANY input), and
a `sequential_vmap` rule so `bmrm_path(mode='vmap')` composes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _k
from ..platform import on_tpu as _on_tpu

# Static y-level capacity of the on-chip histogram. Utility scores in
# ranking data are graded relevance judgments (a handful of levels; the
# paper's datasets use <= 5), so 256 covers real inputs with slack while
# keeping the (tiles+1, 256) i32 prefix small. Inputs with more distinct
# y values (e.g. continuous regression targets, or grouped counting
# whose key offsets multiply the alphabet by the group count) fall back
# to the merge-sort tree INSIDE the trace — same outputs, no recompile.
DEFAULT_LEVELS = 256


def _compact_ranks(y: jnp.ndarray) -> jnp.ndarray:
    """Dense 0-based y-ranks, ties sharing a rank.

    Order-isomorphic to y (a > b iff rank(a) > rank(b)), so every
    preference comparison in the kernel is exact regardless of y's dtype
    or spacing — the counts never touch y's float values again.
    """
    ys = jnp.sort(y)
    new = jnp.concatenate([jnp.ones((1,), jnp.int32),
                           (ys[1:] != ys[:-1]).astype(jnp.int32)])
    rank_of_sorted = jnp.cumsum(new) - 1
    first = jnp.searchsorted(ys, y, side='left')
    return jnp.take(rank_of_sorted, first).astype(jnp.int32)


def _kernel_counts(p, y, ti_rows: int, tj_rows: int, levels: int,
                   interpret: bool):
    """The kernel fast path: assumes #distinct(y) <= levels (guarded by
    the caller). Returns (c, d) in the original example order."""
    m = p.shape[0]
    order = jnp.argsort(p)
    ps = jnp.take(p, order)
    yr = jnp.take(_compact_ranks(y), order)

    ti = ti_rows * _k.LANES
    tj = tj_rows * _k.LANES
    row = _k.LANES * max(ti_rows, tj_rows)
    mp = -(-m // row) * row
    # Pads sort after every real score (+inf) and carry rank `levels`
    # (one past any real rank): they satisfy neither count's preference
    # test, and the histogram scatter drops them (index out of range).
    ps_pad = jnp.pad(ps, (0, mp - m), constant_values=jnp.inf)
    yr_pad = jnp.pad(yr, (0, mp - m), constant_values=levels)
    nI = mp // ti
    nJ = mp // tj

    # Cumulative per-candidate-tile y-level histogram: row t = counts of
    # each rank among candidate tiles [0, t). int32 is exact (counts
    # <= m < 2^31).
    tile_of = jnp.arange(mp) // tj
    hist = jnp.zeros((nJ, levels), jnp.int32).at[tile_of, yr_pad].add(
        1, mode='drop')
    pref = jnp.concatenate([jnp.zeros((1, levels), jnp.int32),
                            jnp.cumsum(hist, axis=0)])

    # Frontier bands per query tile, from its extreme queries q0 <= q1:
    # float rounding is monotone (a <= b implies fl(a+1) <= fl(b+1)), so
    # candidate tiles < l_min//tj lie inside the p+1 frontier of every
    # query of the tile, and the partial band [c_lo, c_hi) is compared
    # densely in-kernel with the reference predicates. Same for d with
    # side='right' against p-1 (the exact complement of `p_j > p_i - 1`).
    one = jnp.asarray(1.0, ps_pad.dtype)
    q0 = ps_pad.reshape(nI, ti)[:, 0]
    q1 = ps_pad.reshape(nI, ti)[:, -1]
    l_min = jnp.searchsorted(ps_pad, q0 + one, side='left').astype(jnp.int32)
    l_max = jnp.searchsorted(ps_pad, q1 + one, side='left').astype(jnp.int32)
    r_min = jnp.searchsorted(ps_pad, q0 - one, side='right').astype(jnp.int32)
    r_max = jnp.searchsorted(ps_pad, q1 - one, side='right').astype(jnp.int32)
    band = jnp.stack([l_min // tj, -(-l_max // tj),
                      r_min // tj, -(-r_max // tj)], axis=1).reshape(-1)

    c2, d2 = _k.rank_counts_kernel(band, ps_pad.reshape(-1, _k.LANES),
                                   yr_pad.reshape(-1, _k.LANES), pref,
                                   ti_rows=ti_rows, tj_rows=tj_rows,
                                   interpret=interpret)
    z = jnp.zeros((m,), jnp.int32)
    return (z.at[order].set(c2.reshape(-1)[:m]),
            z.at[order].set(d2.reshape(-1)[:m]))


def _rank_counts_impl(p, y, *, ti_rows: int, tj_rows: int, levels: int,
                      interpret: bool):
    p = p.astype(jnp.float32) if p.dtype == jnp.float64 else p
    y = y.astype(jnp.float32) if y.dtype == jnp.float64 else y
    m = p.shape[0]
    if m == 0:
        z = jnp.zeros((0,), jnp.int32)
        return z, z
    # core.counts is imported lazily: core late-imports THIS module from
    # counts_dispatch, and neither package pays for the other at import.
    from repro.core import counts as _tree
    n_distinct = jnp.max(_compact_ranks(y)) + 1
    return jax.lax.cond(
        n_distinct <= levels,
        lambda: _kernel_counts(p, y, ti_rows, tj_rows, levels, interpret),
        lambda: _tree.counts_fused(p, y))


@functools.partial(jax.jit, static_argnames=('ti_rows', 'tj_rows',
                                             'levels', 'interpret'))
def rank_counts(p: jnp.ndarray, y: jnp.ndarray, ti_rows: int = 8,
                tj_rows: int = 8, levels: int = DEFAULT_LEVELS,
                interpret: bool | None = None):
    """Fused (c, d) counts via the tiled rank-counting Pallas kernel.

    Both frequency vectors from one sort + one on-chip pass
    (kernel.py); bit-identical to `ref.counts_ref` for any real-valued
    p, y — inputs whose distinct-y alphabet exceeds `levels` take an
    in-trace `counts_fused` fallback (`lax.cond`), so exactness never
    depends on the histogram capacity.

    Batching: wrapped in `jax.custom_batching.sequential_vmap`, so
    `vmap(rank_counts)` — and through it the batched lambda path sweep
    `bmrm_path(mode='vmap')` — lowers to a scan of kernel calls on any
    backend instead of relying on a pallas batching rule.
    """
    if interpret is None:
        interpret = not _on_tpu()
    fn = jax.custom_batching.sequential_vmap(
        functools.partial(_rank_counts_impl, ti_rows=ti_rows,
                          tj_rows=tj_rows, levels=levels,
                          interpret=interpret))
    return fn(p, y)


@functools.partial(jax.jit, static_argnames=('ti_rows', 'tj_rows',
                                             'levels', 'interpret'))
def rank_counts_grouped(p: jnp.ndarray, y: jnp.ndarray, g: jnp.ndarray,
                        ti_rows: int = 8, tj_rows: int = 8,
                        levels: int = DEFAULT_LEVELS,
                        interpret: bool | None = None):
    """Grouped (c, d) via the key-offset trick over the fused kernel.

    The offsets make each group's y values a disjoint rank band, so the
    effective alphabet is ~n_groups * levels-per-group; past `levels`
    the in-trace tree fallback keeps results exact (DESIGN.md §8).
    """
    from repro.core.counts import _group_offsets
    pg, yg = _group_offsets(p, y, g)
    return rank_counts(pg, yg, ti_rows=ti_rows, tj_rows=tj_rows,
                       levels=levels, interpret=interpret)
