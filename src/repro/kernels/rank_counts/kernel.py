"""Pallas TPU kernel: fused sub-quadratic RankSVM frequency counts.

One tiled on-chip pass produces BOTH of the paper's frequency vectors
(c, d) — the `counts_fused` complement trick moved into a kernel. The
host-side wrapper (ops.py) sorts the scores once and precomputes two
static structures that replace the paper's red-black tree:

  * the scores and compact y-ranks in sorted-p order, reshaped to
    hardware-aligned (rows, 128) tiles and kept VMEM-resident whole;
  * a cumulative per-candidate-tile y-level histogram `pref`
    (`pref[t][l]` = examples with y-rank l among the first t candidate
    tiles) — a merge-sort tree flattened to its leaf counts, buildable
    in O(m) and queryable without gathers (TPU lane constraints rule
    out the per-element binary searches of core.counts inside a
    kernel).

Because the data is sorted by p, each query tile's two margin frontiers
(p + 1 to the left, p - 1 to the right) span a contiguous band of
candidate tiles, found with four searchsorteds per tile on host and
prefetched as SMEM scalars (`band`). The kernel then answers BOTH
counts from the same structures:

  c_i = (histogram prefix of tiles fully inside the p+1 frontier,
         levels > rank_i)  +  dense compare over the partial band
  d_i = (histogram SUFFIX of tiles fully inside the p-1 frontier,
         levels < rank_i)  +  dense compare over its partial band

The dense band work uses the reference comparisons verbatim
(`p_j < p_i + 1`, `p_j > p_i - 1` in f32), and the histogram terms count
whole tiles whose membership was decided by `searchsorted` against the
same rounded f32 thresholds — float rounding is monotone, so a tile
strictly inside the frontier for the extreme query of the block is
inside it for every query. Counts are therefore bit-identical to
`ref.counts_ref` under the paper's exact tie semantics.

Work: O(m log m) for the host-side sort + O(m·levels/tj + m·band) on
chip, vs the O(m^2) of the pairwise kernel; a tie-free worst case
(every frontier boundary mid-tile) degrades the band term to one dense
tile row per query tile, never to a full pairwise pass.

Grid: 1-D over query tiles. Layouts (what the v5e compiler accepts):
query tiles arrive as lane-dense (ti_rows, 128) blocks and become
(128, ti_rows, 1) columns in the kernel (`pairwise_rank.kernel.
query_column`); the candidate scores and ranks are (m/TJ, TJ) arrays
read one (1, TJ) candidate tile per band step (a dynamic row slice), and
the histogram rows broadcast as (1, 1, levels). Nothing flattens a
(rows, 128) tile to 1-D. The candidate arrays and the (tiles+1, levels)
prefix stay whole in VMEM, single-buffered because their block never
moves: at m = 2^20 with TJ = 1024 and 256 levels that is 4 MiB of f32
scores, 4 MiB of i32 ranks and 1 MiB of prefix. For the tiles and the
band-compare temporaries the v5e compiler reports 2.4 MiB of
kernel-scoped VMEM when XLA has already placed those operands in VMEM,
and 6.3 MiB inside the full `rank_counts` program; `_vmem_limit` sizes
the scoped limit from the resident bytes so the kernel compiles either
way (DESIGN.md §8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..pairwise_rank.kernel import query_column

LANES = 128
# v5e has 128 MiB of VMEM per core; the scoped default is 16 MiB.
_VMEM_CAP = 100 * 1024 * 1024


def _rank_counts_kernel(band_ref, ps_q_ref, yr_q_ref, ps_all_ref,
                        yr_all_ref, pref_ref, c_ref, d_ref, *,
                        levels: int):
    i = pl.program_id(0)
    ps_q = query_column(ps_q_ref)      # (128, TI_ROWS, 1) sorted scores
    yr_q = query_column(yr_q_ref)      # their y-ranks

    # Per-query-tile candidate-tile band [lo, hi) for each frontier,
    # prefetched to SMEM: tiles < c_lo are fully inside the p+1 frontier
    # of EVERY query in this tile, tiles >= d_hi fully inside the p-1
    # frontier; the partial bands are compared densely below.
    c_lo = band_ref[4 * i]
    c_hi = band_ref[4 * i + 1]
    d_lo = band_ref[4 * i + 2]
    d_hi = band_ref[4 * i + 3]

    def pref_row(t):
        return pref_ref[pl.ds(t, 1), :][None]      # (1, 1, levels)

    lvl = jax.lax.broadcasted_iota(jnp.int32, (1, 1, levels), 2)
    # c prefix: candidates in tiles [0, c_lo), counted by y-level.
    c_acc = jnp.sum(jnp.where(lvl > yr_q, pref_row(c_lo), 0), axis=2,
                    dtype=jnp.int32)
    # d suffix: candidates in tiles [d_hi, nJ) = total minus prefix —
    # the complement trick, answered from the SAME histogram.
    p_d = pref_row(pref_ref.shape[0] - 1) - pref_row(d_hi)
    d_acc = jnp.sum(jnp.where(lvl < yr_q, p_d, 0), axis=2, dtype=jnp.int32)

    # Partial bands: the reference comparisons, one (1, TJ) candidate
    # tile at a time over dynamically-bounded tile ranges.
    def c_body(j, acc):
        ps_j = ps_all_ref[pl.ds(j, 1), :][None]    # (1, 1, TJ)
        yr_j = yr_all_ref[pl.ds(j, 1), :][None]
        hit = (yr_j > yr_q) & (ps_j < ps_q + 1.0)
        return acc + jnp.sum(hit, axis=2, dtype=jnp.int32)

    c_acc = jax.lax.fori_loop(c_lo, c_hi, c_body, c_acc)

    def d_body(j, acc):
        ps_j = ps_all_ref[pl.ds(j, 1), :][None]
        yr_j = yr_all_ref[pl.ds(j, 1), :][None]
        hit = (yr_j < yr_q) & (ps_j > ps_q - 1.0)
        return acc + jnp.sum(hit, axis=2, dtype=jnp.int32)

    d_acc = jax.lax.fori_loop(d_lo, d_hi, d_body, d_acc)

    c_ref[...] = c_acc.T
    d_ref[...] = d_acc.T


def _vmem_limit(n_tiles: int, tj: int, levels: int) -> int:
    """Scoped-VMEM limit for one call: the whole-resident candidate
    arrays and prefix (single-buffered), plus 8 MiB for the pipelined
    query tiles and the band-compare temporaries (the v5e compiler
    reports 2.4-6.3 MiB of scoped VMEM for those at the default tiles,
    m = 2^20); never below the 16 MiB default, capped under the
    128 MiB of the core."""
    resident = 4 * (2 * n_tiles * tj + (n_tiles + 1) * levels)
    return int(min(max(resident + (8 << 20), 16 << 20), _VMEM_CAP))


def rank_counts_kernel(band: jnp.ndarray, ps2: jnp.ndarray,
                       yr2: jnp.ndarray, pref: jnp.ndarray,
                       ti_rows: int = 8, tj_rows: int = 8,
                       interpret: bool = True):
    """Raw pallas_call on pre-sorted, pre-padded (rows, 128) inputs.

    Args:
      band: (4 * rows/ti_rows,) int32 per-query-tile candidate-tile
        bands [c_lo, c_hi, d_lo, d_hi], flattened (scalar-prefetched to
        SMEM, where a 2-D (tiles, 4) array would pad its minor axis to
        128 words and overflow SMEM's 1 MiB past m = 2^21).
      ps2: (R, 128) float32 scores in ascending order, padded with +inf;
        R % max(ti_rows, tj_rows) == 0.
      yr2: (R, 128) int32 compact y-ranks in the same order, pads
        = `levels` (one past any real rank).
      pref: (R/tj_rows + 1, levels) int32 cumulative per-candidate-tile
        y-level histogram; row t counts tiles [0, t), pads excluded.
      ti_rows / tj_rows: query / candidate tile heights in 128-lane rows
        (ti_rows a multiple of 8 on the chip). Defaults (8, 8):
        1024-element tiles, whose dense-band compare is a
        (128, 8, 1024) mask.
      interpret: run the kernel body in Python (CPU validation mode).
    """
    rows = ps2.shape[0]
    levels = pref.shape[1]
    tj = tj_rows * LANES
    n_tiles = rows // tj_rows
    query = pl.BlockSpec((ti_rows, LANES), lambda i, band: (i, 0))

    def whole(shape):
        # The block never moves, so one buffer is enough.
        return pl.BlockSpec(shape, lambda i, band: (0, 0),
                            pipeline_mode=pl.Buffered(1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // ti_rows,),
        in_specs=[query, query, whole((n_tiles, tj)), whole((n_tiles, tj)),
                  whole(pref.shape)],
        out_specs=[query, query],
    )
    c2, d2 = pl.pallas_call(
        functools.partial(_rank_counts_kernel, levels=levels),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(n_tiles, tj, levels)),
        interpret=interpret,
    )(band, ps2, yr2, ps2.reshape(n_tiles, tj), yr2.reshape(n_tiles, tj),
      pref)
    return c2, d2
