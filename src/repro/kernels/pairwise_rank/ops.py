"""Jit'd public wrappers around the pairwise_rank Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _k
from ..platform import on_tpu as _on_tpu


@functools.partial(jax.jit,
                   static_argnames=('ti_rows', 'tj_rows', 'interpret'))
def pairwise_counts(p: jnp.ndarray, y: jnp.ndarray,
                    ti_rows: int = 8, tj_rows: int = 8,
                    interpret: bool | None = None):
    """O(m^2) (c, d) counts via the tiled Pallas kernel.

    Handles padding: p -> +inf, y -> +inf so padded candidates satisfy
    neither count: for c the margin p_j < p_i + 1 fails (p_j = +inf), for d
    the preference y_j < y_i fails (y_j = +inf).
    """
    if interpret is None:
        interpret = not _on_tpu()
    m = p.shape[0]
    row = _k.LANES * max(ti_rows, tj_rows)
    mp = -(-max(m, 1) // row) * row
    p2 = jnp.pad(p.astype(jnp.float32), (0, mp - m),
                 constant_values=jnp.inf).reshape(-1, _k.LANES)
    y2 = jnp.pad(y.astype(jnp.float32), (0, mp - m),
                 constant_values=jnp.inf).reshape(-1, _k.LANES)
    c2, d2 = _k.pairwise_counts_kernel(p2, y2, ti_rows=ti_rows,
                                       tj_rows=tj_rows, interpret=interpret)
    return c2.reshape(-1)[:m], d2.reshape(-1)[:m]


@functools.partial(jax.jit, static_argnames=('interpret',))
def pairwise_rank_loss(p: jnp.ndarray, y: jnp.ndarray, n_pairs,
                       interpret: bool | None = None):
    """RankSVM R_emp via kernel counts + Lemma 1."""
    c, d = pairwise_counts(p, y, interpret=interpret)
    cf, df = c.astype(jnp.float32), d.astype(jnp.float32)
    return jnp.sum((cf - df) * p.astype(jnp.float32) + cf) / n_pairs


# Crossover point (elements) below which the dense O(m^2) kernel wins over
# the gather-bound merge-sort-tree on TPU; measured in fig5_crossover.
KERNEL_MAX_M = 4096


def counts_auto(p: jnp.ndarray, y: jnp.ndarray):
    """Measured engine tiering behind `counts_dispatch(engine='auto')`.

    TPU: the dense Pallas pairwise kernel up to KERNEL_MAX_M elements
    (the fig5_crossover win band), the fused rank-counts kernel
    (`kernels.rank_counts`, DESIGN.md §8) above it — one tiled on-chip
    pass for both frequency vectors, with its own in-trace tree
    fallback when the distinct-y alphabet overflows the histogram.

    Other backends: the gather-free merge-sort count (`counts_fused`).
    The rank-counts kernel only runs through the Pallas interpreter off
    TPU; its measured interpret-mode per-call win at mid m does not
    survive the extra compile latency and inverts at m ~ 1e6, so
    CPU-auto staying on the tree is the recorded dispatch exception
    (EXPERIMENTS.md §Counts kernel).
    """
    from repro.core import counts as _tree
    if _on_tpu():
        if p.shape[0] <= KERNEL_MAX_M:
            return pairwise_counts(p, y)
        from ..rank_counts import ops as _rc_ops
        return _rc_ops.rank_counts(p, y)
    return _tree.counts_fused(p, y)
