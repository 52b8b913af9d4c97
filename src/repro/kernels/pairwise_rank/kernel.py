"""Pallas TPU kernel: tiled O(m^2) RankSVM frequency counts.

Computes the paper's c/d vectors (eqs. 5-6) by brute-force pairwise
comparison, tiled for VMEM. This is (a) the PairRSVM baseline the paper
benchmarks against, and (b) the *fast path* for small ranking groups on TPU:
for m <= a few thousand the dense 8x128-lane compare+reduce beats the
gather-bound merge-sort-tree queries of core.counts (see DESIGN.md §2 and
benchmarks/fig5_crossover.py).

Tiling: grid (m/TI, m/TJ); each step compares a tile of TI queries i with
TJ candidates j, reduces over j, and accumulates into the i-indexed output
block. TPU grids iterate the trailing axis sequentially, so the j-axis
accumulation into the i-indexed output block is the canonical revisiting
pattern.

Layouts (what the v5e compiler accepts): queries arrive as lane-dense
(ti_rows, 128) blocks of the (m/128, 128) scores and are turned into a
(128, ti_rows, 1) column IN the kernel (`query_column`: a 2-D transpose
plus a unit minor axis); candidates arrive as (1, TJ) blocks of the (1, m)
scores and broadcast as (1, 1, TJ). The (128, ti_rows, TJ) compare thus
broadcasts without flattening a (rows, 128) tile to 1-D, which Mosaic
rejects ("unsupported shape cast"), and without padding a query column's
lanes in HBM. ti_rows must be a multiple of 8 on the chip (the f32
sublane tile); any value runs in interpret mode. Padding convention (see
ops.py): p_pad = +inf, y_pad = +inf never contributes to either count.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def query_column(tile_ref) -> jnp.ndarray:
    """A lane-dense (rows, 128) query tile as a (128, rows, 1) column.

    Element [l, r, 0] is query r * 128 + l, so a (128, rows) reduction
    over the candidate axis transposes back (`.T`) to the tile's own
    (rows, 128) order."""
    return tile_ref[...].T[:, :, None]


def _pairwise_kernel(p_i_ref, y_i_ref, p_j_ref, y_j_ref, c_ref, d_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        d_ref[...] = jnp.zeros_like(d_ref)

    p_i = query_column(p_i_ref)       # (128, TI_ROWS, 1)
    y_i = query_column(y_i_ref)
    p_j = p_j_ref[...][None]          # (1, 1, TJ)
    y_j = y_j_ref[...][None]

    # c_i += |{j : y_j > y_i  and  p_j < p_i + 1}|
    c_tile = jnp.sum((y_j > y_i) & (p_j < p_i + 1.0), axis=2,
                     dtype=jnp.int32)
    # d_i += |{j : y_j < y_i  and  p_j > p_i - 1}|
    d_tile = jnp.sum((y_j < y_i) & (p_j > p_i - 1.0), axis=2,
                     dtype=jnp.int32)

    c_ref[...] += c_tile.T
    d_ref[...] += d_tile.T


def pairwise_counts_kernel(p2: jnp.ndarray, y2: jnp.ndarray,
                           ti_rows: int = 8, tj_rows: int = 8,
                           interpret: bool = True):
    """Raw pallas_call on pre-padded (rows, 128) inputs.

    Args:
      p2, y2: (R, 128) float32, R % max(ti_rows, tj_rows) == 0.
      ti_rows / tj_rows: query / candidate tile heights in 128-lane rows.
        Defaults (8, 8): a (1024, 1024) comparison per grid step, held
        as (128, 8, 1024) masks, for which the v5e compiler reports
        1.6 MiB of scoped VMEM (DESIGN.md §8).
      interpret: run the kernel body in Python (CPU validation mode).
    """
    rows = p2.shape[0]
    tj = tj_rows * LANES
    grid = (rows // ti_rows, rows // tj_rows)
    query = pl.BlockSpec((ti_rows, LANES), lambda i, j: (i, 0))
    cand = pl.BlockSpec((1, tj), lambda i, j: (0, j))
    c2, d2 = pl.pallas_call(
        _pairwise_kernel,
        grid=grid,
        in_specs=[query, query, cand, cand],
        out_specs=[query, query],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(p2, y2, p2.reshape(1, -1), y2.reshape(1, -1))
    return c2, d2
