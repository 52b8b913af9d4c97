"""Pod-scale distributed RankSVM: the paper's Algorithm 3 on a TPU mesh.

Decomposition (DESIGN.md §5): for the BMRM oracle at scale the heavy objects
are the data matrix X (m x n, hundreds of GB) and its two matvecs; the score
vectors p, y are tiny (4 MB at m = 1M). So:

  * X is 2-D sharded: rows over 'data' (and 'pod'), columns over 'model'.
  * p = X w needs a partial-sum all-reduce over 'model' (w is
    column-sharded), leaving p row-sharded — O(m/devices) per device.
  * the counts c, d: p and y are all-gathered (4 MB — cheap) and the
    merge-sort-tree queries run with QUERIES sharded over the mesh: each
    device answers m/devices rank queries against the replicated tree
    levels. Work per device: O((m/devs) log^2 m) — the paper's linearithmic
    bound, parallelized.
  * the subgradient a = X^T (c - d)/N contracts over row-sharded m ->
    reduce-scatter/all-reduce over 'data', leaving a column-sharded like w.

One oracle call therefore costs O(ms/devs) flops + two small collectives +
one O(m) gather — the TPU-native replacement for the paper's single-machine
red-black tree sweep.

Per-query LTR at pod scale: group ids ride along exactly like y (row-sharded
in, all-gathered for the counting phase), and the key-offset trick
(`counts._group_offsets`) folds the per-group restriction into the SAME
single tree pass — cross-group pairs are pushed outside the margin/preference
conditions by construction, so the sharded cost model above is unchanged.

`make_oracle_body` is the composable (unjitted) form of the step: bmrm's
device driver inlines it into its jitted `bundle_step` via
`ShardedOracle.step_fn`, with the bundle state carrying the matching
sharding annotations (`core.bmrm.bundle_state_shardings`).

Sparse features stay sparse (DESIGN.md §9): `make_csr_oracle_body` is
the same oracle over a row-sharded padded CSR slot layout
(`csr_slot_arrays`) whose matvecs cost O(nnz) instead of dense m·n —
only the two matvecs differ, the counting/loss core
(`_scores_to_coeffs`) is shared. And X never has to be host-resident:
`assemble_row_sharded` streams each host's row ranges out of a
`RowBlockSource` (prefetched) and stitches the device shards with
`jax.make_array_from_single_device_arrays`.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from . import counts as _counts
from ..data import rowblocks as _rowblocks

f32 = jnp.float32

# The mesh oracle bodies implement only the uniform pairwise hinge: the
# partitioned counting path has no weighted-prefix or segmented-running-max
# lowering, and silently computing the wrong objective at pod scale is the
# worst possible failure mode. `validate_sharded_loss` is the single gate —
# ShardedOracle and make_oracle both call it BEFORE any densify, padding,
# or device transfer (DESIGN.md §12).
SHARDED_LOSSES = ('hinge',)


def validate_sharded_loss(loss: str) -> None:
    """Reject losses the sharded mesh bodies do not implement, up front."""
    if loss not in SHARDED_LOSSES:
        raise ValueError(
            f'the sharded mesh oracle supports only loss in '
            f'{SHARDED_LOSSES}, got {loss!r}; train this loss with '
            "method='tree'/'pairs'/'auto'/'stream' instead (the fused and "
            'streaming oracles implement every loss in oracle.LOSSES)')


def validate_mesh_axes(mesh) -> None:
    """Reject a mesh with `Explicit` axes, up front: the mesh bodies place
    their arrays with `with_sharding_constraint`, which accepts only
    `Auto` axes (and `jax.make_mesh` makes `Explicit` ones by default)."""
    explicit = [name for name, kind in zip(mesh.axis_names, mesh.axis_types)
                if kind == AxisType.Explicit]
    if explicit:
        raise ValueError(
            f'the sharded oracle needs Auto mesh axes, but {explicit} are '
            'Explicit; build the mesh with repro.launch.mesh.make_mesh, or '
            'pass axis_types=(jax.sharding.AxisType.Auto, ...) to '
            'jax.make_mesh')


@dataclasses.dataclass(frozen=True)
class RankSVMShapeConfig:
    name: str
    m: int                      # training examples (rows)
    n: int                      # features (columns)
    kind: str = 'oracle'


def input_specs(mcfg, shape: RankSVMShapeConfig):
    """ShapeDtypeStruct stand-ins for one BMRM oracle evaluation."""
    return {
        'X': jax.ShapeDtypeStruct((shape.m, shape.n), jnp.bfloat16),
        'y': jax.ShapeDtypeStruct((shape.m,), f32),
        'g': jax.ShapeDtypeStruct((shape.m,), jnp.int32),
        'w': jax.ShapeDtypeStruct((shape.n,), f32),
        'n_pairs': jax.ShapeDtypeStruct((), f32),
    }


def arg_shardings(mesh):
    rows = tuple(a for a in ('pod', 'data') if a in mesh.axis_names)
    return {
        'X': NamedSharding(mesh, P(rows, 'model')),
        'y': NamedSharding(mesh, P(rows)),
        'g': NamedSharding(mesh, P(rows)),       # group ids ride like y
        'w': NamedSharding(mesh, P('model')),
        'n_pairs': NamedSharding(mesh, P()),
        # CSR layout (make_csr_oracle_body): the padded per-row slot
        # arrays shard row-wise like y — the slot axis is tiny (max
        # nnz/row) and stays local, so the O(nnz) segment-sum matvecs
        # run on each device's own rows.
        'data2': NamedSharding(mesh, P(rows, None)),
        'idx2': NamedSharding(mesh, P(rows, None)),
    }


def out_shardings(mesh):
    return (NamedSharding(mesh, P()),            # loss
            NamedSharding(mesh, P('model')))     # subgradient (like w)


def make_oracle_body(mesh, variant: str = 'base', engine: str = 'tree'):
    """Traced `(X, y, g, w, n_pairs) -> (loss, a)` — the paper's Algorithm 3
    sharded over `mesh`, composable inside a larger jitted program (bmrm's
    device `bundle_step` inlines it via `ShardedOracle.step_fn`).

    `g` is the per-row group-id vector (row-sharded like y) or None; with
    groups the counting phase applies the key-offset trick to the
    all-gathered scores, so per-query LTR costs the same single tree pass.

    variant='base': the paper-faithful port — matvecs sharded, the counts
    computation left to the partitioner (it replicates the query work on
    every device; see EXPERIMENTS.md §Perf cell C baseline).
    variant='opt' : beyond-paper — every query-indexed array inside the
    merge-sort-tree is sharding-constrained over the mesh rows, so each
    device answers m/devices rank queries against the replicated (4 MB)
    tree levels. Identical outputs; O(devices) less query work per device.

    engine='tree' (default) is the sharded production path above. Any
    other `counts.ENGINES` entry runs `counts_dispatch` on the
    all-gathered (replicated) offset keys instead — the Pallas kernels
    have no partitioning rule, so their count work replicates across
    devices like variant='base' does; the matvecs (the O(m n) term)
    stay sharded either way. `variant='opt'` query sharding applies to
    the tree engine only.
    """
    core = _scores_to_coeffs(mesh, variant=variant, engine=engine)
    rows = tuple(a for a in ('pod', 'data') if a in mesh.axis_names)

    def oracle(X, y, g, w, n_pairs):
        # p = X w : contraction over the column-sharded n axis -> all-reduce
        # over 'model'; result stays row-sharded.
        p = jnp.einsum('mn,n->m', X, w.astype(jnp.bfloat16),
                       preferred_element_type=f32)
        p = jax.lax.with_sharding_constraint(p, NamedSharding(mesh, P(rows)))
        loss, cd = core(p, y, g, n_pairs)
        # a = X^T cd / N : contraction over row-sharded m -> collective over
        # 'data'/'pod'; result column-sharded like w.
        a = jnp.einsum('mn,m->n', X, (cd / n_pairs).astype(jnp.bfloat16),
                       preferred_element_type=f32)
        a = jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P('model')))
        return loss, a

    return oracle


def _scores_to_coeffs(mesh, variant: str = 'base', engine: str = 'tree'):
    """The layout-independent middle of every sharded oracle body:
    row-sharded scores -> (loss, row-sharded pair-count coefficients).

    Gathers the tiny per-row vectors, folds group ids in via the
    key-offset trick, runs the counting engine (queries sharded over the
    mesh rows under variant='opt'), and evaluates the Lemma 1 loss. Both
    `make_oracle_body` (dense bf16 einsum matvecs) and
    `make_csr_oracle_body` (padded-slot segment-sum matvecs) wrap this
    core — the feature layout only ever touches the two matvecs.
    """
    _counts._validate_engine(engine)
    rows = tuple(a for a in ('pod', 'data') if a in mesh.axis_names)
    cns = None
    if variant == 'opt':
        def cns(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*((rows,) + (None,) * (x.ndim - 1)))))

    def core(p, y, g, n_pairs):
        # counts: gather the tiny score vectors, shard the queries.
        p_rep = jax.lax.with_sharding_constraint(
            p, NamedSharding(mesh, P()))
        y_rep = jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P()))
        if g is not None:
            # Per-group counting = the same tree pass over offset keys
            # (counts._group_offsets): cross-group pairs fall outside the
            # margin/preference windows, within-group comparisons unchanged.
            g_rep = jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, P()))
            pk, yk = _counts._group_offsets(p_rep, y_rep, g_rep)
        else:
            pk, yk = p_rep, y_rep
        if engine != 'tree':
            c, d = _counts.counts_dispatch(pk, yk, None, engine=engine)
        elif cns is None:
            c, d = _counts.counts_fused(pk, yk)
        else:
            c = _counts._half_counts(pk, yk, constrain=cns)
            d = _counts._half_counts(-pk, -yk, constrain=cns)
        cd = (c - d).astype(f32)
        cd = jax.lax.with_sharding_constraint(
            cd, NamedSharding(mesh, P(rows)))

        # Loss uses the ORIGINAL scores p: within-group offsets cancel in
        # the hinge terms, exactly as in the single-host grouped oracle.
        loss = jnp.sum(cd * p_rep + c.astype(f32)) / n_pairs
        return loss, cd

    return core


def make_csr_oracle_body(mesh, variant: str = 'base', engine: str = 'tree'):
    """Traced `(data2, idx2, y, g, w, n_pairs) -> (loss, a)` — the sharded
    oracle on CSR features at O(nnz) matvec cost, no densification.

    Layout (DESIGN.md §9): CSR rows are padded to a uniform slot count
    s = max nnz/row — `data2` (m, s) bf16 values, `idx2` (m, s) int32
    column ids — and both shard row-wise like y (`arg_shardings`), so
    each device owns its rows' nonzeros outright. Pad slots carry
    (0.0, 0): they contribute 0 to both matvecs, exactly like the dense
    body's zero pad rows. Memory is 6 bytes/slot (bf16 value + int32 id)
    vs 2 bytes/column dense, so the layout wins below ~n/3 nonzeros per
    row — tf-idf text is orders of magnitude below that.

    Matvec: gather w (replicated — O(n) floats, the cheap collective)
    per nonzero and einsum over the slot axis, bf16 products with f32
    accumulation — the same precision trade as the dense body.
    Transpose-matvec: f32 products segment-summed into the n feature
    bins (partial sums per row shard, reduced over 'data'/'pod'),
    constrained column-sharded like w. Counting/loss run through the
    same `_scores_to_coeffs` core as the dense body, so grouped
    counting, `variant='opt'` query sharding, and engine dispatch
    compose unchanged.
    """
    core = _scores_to_coeffs(mesh, variant=variant, engine=engine)
    rows = tuple(a for a in ('pod', 'data') if a in mesh.axis_names)

    def oracle(data2, idx2, y, g, w, n_pairs):
        n = w.shape[0]
        wb = w.astype(jnp.bfloat16)
        p = jnp.einsum('ms,ms->m', data2, wb[idx2],
                       preferred_element_type=f32)
        p = jax.lax.with_sharding_constraint(p, NamedSharding(mesh, P(rows)))
        loss, cd = core(p, y, g, n_pairs)
        prod = data2.astype(f32) * (cd / n_pairs)[:, None]
        a = jax.ops.segment_sum(prod.reshape(-1), idx2.reshape(-1),
                                num_segments=n)
        a = jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P('model')))
        return loss, a

    return oracle


def csr_slot_arrays(data, indices, indptr, shape, *, pad_rows: int = 0):
    """Host-side packing of CSR (data, indices, indptr) into the padded
    per-row slot arrays consumed by `make_csr_oracle_body`.

    Returns `(data2, idx2)`: (m + pad_rows, s) float32/int32 with
    s = max(1, max nnz/row); pad slots and the `pad_rows` trailing
    zero-feature rows (the mesh row-multiple padding) carry (0.0, 0).
    The caller casts data2 to bf16 at device_put, keeping the one f32
    copy host-side and transient.
    """
    m, _ = map(int, shape)
    data = np.asarray(data, np.float32)
    indices = np.asarray(indices, np.int64)
    indptr = np.asarray(indptr, np.int64)
    lens = np.diff(indptr)
    s = max(1, int(lens.max())) if m else 1
    data2 = np.zeros((m + pad_rows, s), np.float32)
    idx2 = np.zeros((m + pad_rows, s), np.int32)
    if m and data.size:
        rows = np.repeat(np.arange(m, dtype=np.int64), lens)
        slots = np.arange(data.size, dtype=np.int64) - np.repeat(
            indptr[:-1], lens)
        data2[rows, slots] = data
        idx2[rows, slots] = indices
    return data2, idx2


def assemble_row_sharded(source, sharding, shape, *, block_rows: int,
                         prefetch=0):
    """Assemble the 2-D row-sharded bf16 feature array from a
    `RowBlockSource`, one HOST-LOCAL shard at a time — the per-host
    streamed input path of `ShardedOracle` (DESIGN.md §9).

    The per-host source contract: each host walks
    `sharding.addressable_devices_indices_map` — its own devices only —
    groups devices by row range so every row range is read ONCE per
    host, streams that range's blocks out of `source` (read ahead
    `prefetch` blocks by a `data.rowblocks._ReadAhead` thread), and
    `device_put`s each device's column slice of the assembled bf16 slab.
    `jax.make_array_from_single_device_arrays` stitches the global array
    without any host materializing X: peak host residency is one
    per-device-group row range (f32 assembly slab + its bf16 cast) plus
    the in-flight blocks, not the m x n matrix. Rows at or past
    `source.m` (the mesh row-multiple padding) stay zero — identical to
    the dense path's zero-feature pad rows.
    """
    m_pad, n = map(int, shape)
    block_rows = _rowblocks._validate_block_rows(block_rows)
    depth = _rowblocks.resolve_prefetch(source, prefetch)
    by_rows = {}
    imap = sharding.addressable_devices_indices_map((m_pad, n))
    for dev, idx in imap.items():
        rsl, csl = idx[0], idx[1]
        key = (rsl.start or 0, m_pad if rsl.stop is None else rsl.stop)
        by_rows.setdefault(key, []).append((dev, csl))
    shards = []
    for (r0, r1), devs in sorted(by_rows.items()):
        slab = np.zeros((r1 - r0, n), np.float32)
        hi_real = min(r1, source.m)
        spans = [(lo, min(lo + block_rows, hi_real))
                 for lo in range(r0, hi_real, block_rows)]
        ra = (_rowblocks._ReadAhead(lambda i: source.block(*spans[i]),
                                    len(spans), depth)
              if depth and len(spans) > 1 else None)
        try:
            for i, (lo, hi) in enumerate(spans):
                blk = ra.get(i) if ra is not None else source.block(lo, hi)
                slab[lo - r0:hi - r0] = blk
        finally:
            if ra is not None:
                ra.close()
        slab = slab.astype(ml_dtypes.bfloat16)   # RN ties-to-even, same
        for dev, csl in devs:                    # rounding as jnp's cast
            shards.append(jax.device_put(
                np.ascontiguousarray(slab[:, csl]), dev))
    return jax.make_array_from_single_device_arrays(
        (m_pad, n), sharding, shards)


def make_oracle_step(mesh, variant: str = 'base'):
    """Ungrouped 4-arg form of `make_oracle_body` (kept for the oracle-only
    dry-run cells and existing callers)."""
    body = make_oracle_body(mesh, variant=variant)

    def oracle(X, y, w, n_pairs):
        return body(X, y, None, w, n_pairs)

    return oracle


# Dry-run shape: 2x the paper's largest Reuters run, Reuters-like width.
REUTERS_1M = RankSVMShapeConfig('reuters_1m', m=1 << 20, n=49152)
