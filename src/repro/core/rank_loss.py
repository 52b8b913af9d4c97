"""Differentiable pairwise hinge (RankSVM) loss at linearithmic cost.

`pairwise_hinge_loss(scores, utilities)` evaluates eq. (4) of the paper via
Lemma 1 and exposes Lemma 2's subgradient through a `jax.custom_vjp`:

    forward :  O(m log^2 m)   loss = (1/N) sum_i ((c_i - d_i) p_i + c_i)
    backward:  d loss / d p_i = (c_i - d_i) / N          (a valid subgradient)

This is the paper's O(m^2) -> O(m log m) trick made *differentiable*, so any
neural scorer (reward model, reranker head) can be trained end-to-end against
the exact RankSVM objective over the whole global batch. The pairwise hinge is
piecewise linear in p; on the (measure-zero) non-smooth set the returned vector
is still a valid subgradient, which is exactly what subgradient-based
optimizers (SGD/Adam/BMRM) require.

The `group_ids` argument restricts pairs to a single ranking group (e.g. one
query / one prompt) while keeping a single dense linearithmic pass — see
core.counts.counts_grouped_fused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import counts as _counts


def _compact_ids(g: jnp.ndarray) -> jnp.ndarray:
    """Relabel group ids onto [0, n_groups), traceably (static size).

    The key-offset tricks downstream (counts._group_offsets and
    num_pairs_grouped) scale their f32 offset keys with the id VALUES, so
    hashed/sparse ids (e.g. ~1e7) would push one ulp of the keys past the
    hinge margin and quietly corrupt every grouped count. After this only
    the number of distinct groups matters.
    """
    return jnp.unique(g, return_inverse=True,
                      size=g.shape[0])[1].reshape(g.shape).astype(jnp.int32)


def _loss_from_counts(p, c, d, n):
    cf = c.astype(jnp.float32)
    df = d.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    return jnp.sum((cf - df) * pf + cf) / n


def _forward(scores, utilities, group_ids):
    p = scores.astype(jnp.float32)
    if group_ids is None:
        c, d = _counts.counts_fused(p, utilities)
        n = jnp.maximum(_counts.num_pairs(utilities), 1.0)
    else:
        group_ids = _compact_ids(group_ids)
        c, d = _counts.counts_grouped_fused(p, utilities, group_ids)
        n = jnp.maximum(_counts.num_pairs_grouped(utilities, group_ids), 1.0)
    return _loss_from_counts(p, c, d, n), (c, d, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rank_hinge(scores, utilities, group_ids, use_groups: bool):
    loss, _ = _forward(scores, utilities, group_ids if use_groups else None)
    return loss


def _rank_hinge_fwd(scores, utilities, group_ids, use_groups: bool):
    loss, (c, d, n) = _forward(scores, utilities,
                               group_ids if use_groups else None)
    sub = (c.astype(scores.dtype) - d.astype(scores.dtype)) / n.astype(
        scores.dtype)
    return loss, sub


def _rank_hinge_bwd(use_groups: bool, sub, g):
    # Lemma 2: subgradient wrt the scores; utilities / group ids get zeros.
    return (g * sub, jnp.zeros_like(sub), None)


_rank_hinge.defvjp(_rank_hinge_fwd, _rank_hinge_bwd)


def pairwise_hinge_loss(scores: jnp.ndarray, utilities: jnp.ndarray,
                        group_ids: jnp.ndarray | None = None) -> jnp.ndarray:
    """Average pairwise hinge loss (RankSVM R_emp) with linearithmic VJP.

    Args:
      scores:    (m,) predicted utility scores (any float dtype).
      utilities: (m,) ground-truth utility scores — arbitrary reals.
      group_ids: optional (m,) int group labels; only within-group pairs count.
    Returns:
      scalar float32 loss = (1/N) sum_{y_i<y_j, same group} hinge(1+p_i-p_j).
    """
    if group_ids is None:
        dummy = jnp.zeros(scores.shape, jnp.int32)
        return _rank_hinge(scores, utilities, dummy, False)
    return _rank_hinge(scores, utilities, group_ids, True)


def loss_and_subgradient(scores, utilities, group_ids=None):
    """(loss, dloss/dscores) without tracing autodiff — for BMRM / hosts."""
    loss, (c, d, n) = _forward(scores, utilities, group_ids)
    sub = (c.astype(jnp.float32) - d.astype(jnp.float32)) / n
    return loss, sub


def poshinge_weights(utilities, group_ids=None):
    """(v, W): the position-decay pair weights of the 'poshinge' loss.

    v_i = 1 / log2(1 + rank_i), rank_i = |{k in group : y_k > y_i}| + 1 —
    the DCG-style decay of example i's UTILITY rank (a static function of
    the utilities, which is what keeps the training loss convex in w).
    W = sum over preference pairs (i, j), y_i < y_j, of the higher-utility
    side's weight v_j — the normalizer that replaces the pair count N.
    Plain numpy on host (O(m log m)); the traced counterpart lives inside
    `position_weighted_error`.
    """
    from .oracle import _poshinge_weights_norm
    import numpy as _np
    return _poshinge_weights_norm(_np.asarray(utilities),
                                  None if group_ids is None
                                  else _np.asarray(group_ids))


def top1_error(scores, utilities, group_ids=None) -> jnp.ndarray:
    """Top-1 error: fraction of groups whose best-scoring example is not a
    maximum-utility example — the metric the 'toppush' training loss
    optimizes a convex surrogate of.

    Ties in the predicted scores get the AUC-style fractional treatment:
    a group's error is the fraction of its tied top scorers whose utility
    is below the group maximum (0 when every top scorer is optimal, 1 when
    none is). Groups average uniformly; `group_ids=None` is one group.
    """
    p = scores.astype(jnp.float32)
    y = utilities.astype(jnp.float32)
    m = p.shape[0]
    g = (jnp.zeros((m,), jnp.int32) if group_ids is None
         else _compact_ids(group_ids))
    pmax = jax.ops.segment_max(p, g, num_segments=m)
    ymax = jax.ops.segment_max(y, g, num_segments=m)
    top = p == jnp.take(pmax, g)
    bad = top & (y < jnp.take(ymax, g))
    ones = jnp.ones((m,), jnp.float32)
    n_top = jax.ops.segment_sum(jnp.where(top, ones, 0.0), g,
                                num_segments=m)
    n_bad = jax.ops.segment_sum(jnp.where(bad, ones, 0.0), g,
                                num_segments=m)
    size = jax.ops.segment_sum(ones, g, num_segments=m)
    err = jnp.where(size > 0, n_bad / jnp.maximum(n_top, 1.0), 0.0)
    return jnp.sum(err) / jnp.maximum(jnp.sum(
        (size > 0).astype(jnp.float32)), 1.0)


def _utility_rank_weights(y, g):
    """Traced (v, lower): per-example 1/log2(1+utility-rank) weights and
    strictly-lower within-group counts, one stable (g, y) lexsort + four
    segmented scans. The traced twin of `poshinge_weights`."""
    m = y.shape[0]
    order = jnp.lexsort((y, g))
    gs = jnp.take(g, order)
    ys = jnp.take(y, order)
    idx = jnp.arange(m, dtype=jnp.int32)
    one = jnp.ones((1,), bool)
    g_change = jnp.concatenate([one, gs[1:] != gs[:-1]])
    key_change = g_change | jnp.concatenate([one, ys[1:] != ys[:-1]])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(g_change, idx, -1))
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(key_change, idx, -1))
    g_last = jnp.concatenate([gs[:-1] != gs[1:], one])
    key_last = g_last | jnp.concatenate([ys[:-1] != ys[1:], one])
    seg_end = 1 + jax.lax.associative_scan(
        jnp.minimum, jnp.where(g_last, idx, m), reverse=True)
    run_end = 1 + jax.lax.associative_scan(
        jnp.minimum, jnp.where(key_last, idx, m), reverse=True)
    rank = (seg_end - run_end + 1).astype(jnp.float32)
    vs = 1.0 / jnp.log2(1.0 + rank)
    lower = (run_start - seg_start).astype(jnp.float32)
    inv = jnp.zeros((m,), jnp.int32).at[order].set(idx)
    return jnp.take(vs, inv), jnp.take(lower, inv)


def position_weighted_error(scores, utilities, group_ids=None) -> jnp.ndarray:
    """Position-weighted pairwise ranking error — the metric counterpart
    of the 'poshinge' training loss.

    Each swapped preference pair (y_i < y_j but p_i > p_j) costs the
    higher-utility side's position weight v_j = 1/log2(1 + utility rank
    of j) instead of 1; score ties cost half. Normalized by the total
    pair-weight mass W (`poshinge_weights`), so a perfect ranking scores
    0 and a fully reversed one 1; returns 0 when no preference pairs
    exist. Reduces to `ranking_error` when all weights are equal (one
    utility level below the top).
    """
    p = scores.astype(jnp.float32)
    y = utilities.astype(jnp.float32)
    m = p.shape[0]
    g = (jnp.zeros((m,), jnp.int32) if group_ids is None
         else _compact_ids(group_ids))
    v, lower = _utility_rank_weights(y, g)
    W = jnp.sum(v * lower)
    if group_ids is not None:
        p, y = _counts._group_offsets(p, y, g)
    order = jnp.argsort(p)
    ps = jnp.take(p, order)
    ys = jnp.take(y, order)
    vs = jnp.take(v, order)
    lt = jnp.searchsorted(ps, ps, side='left').astype(jnp.int32)
    le = jnp.searchsorted(ps, ps, side='right').astype(jnp.int32)
    # weighted swaps: sum of v_k over {k : p_k < p_i, y_k > y_i} — the
    # same prefix sweep as `ranking_error`, weights riding along
    # (counts._prefix_weighted_greater); [lt, le) are the p-ties, half
    # cost each (k == i contributes 0: y_i > y_i is false).
    wsw = _counts._prefix_weighted_greater(ys, vs, lt, ys)
    wtie = _counts._prefix_weighted_greater(ys, vs, le, ys) - wsw
    total = jnp.sum(wsw) + 0.5 * jnp.sum(wtie)
    return jnp.where(W > 0, total / jnp.where(W > 0, W, 1.0), 0.0)


def ranking_error(scores, utilities, group_ids=None) -> jnp.ndarray:
    """Pairwise ranking error, eq. (1): fraction of swapped pairs.

    Follows the paper's convention: pairs with y_i < y_j count as errors when
    f(x_i) > f(x_j); ties in the *predicted* scores are counted as half an
    error (standard AUC-consistent tie handling).
    """
    p = scores.astype(jnp.float32)
    y = utilities.astype(jnp.float32)
    if group_ids is not None:
        group_ids = _compact_ids(group_ids)
        p, y = _counts._group_offsets(p, y, group_ids)
        n = jnp.maximum(_counts.num_pairs_grouped(utilities, group_ids), 1.0)
    else:
        n = jnp.maximum(_counts.num_pairs(utilities), 1.0)
    # Count swaps with a margin-free variant of the counting machinery:
    # swaps = |{(i,j): y_i < y_j and p_i > p_j}|. Reuse the merge-tree by
    # shrinking the margin to 0 via p' = p / BIG (margin 1 then means ~inf)?
    # Simpler: a swap for pair (i,j), y_i<y_j, is p_j < p_i. Count with the
    # same prefix machinery: sweep sorted p, frontier = strictly-smaller set.
    m = p.shape[0]
    order = jnp.argsort(p)
    ps = jnp.take(p, order)
    ys = jnp.take(y, order)
    lt = jnp.searchsorted(ps, ps, side='left').astype(jnp.int32)   # p_k <  p_i
    le = jnp.searchsorted(ps, ps, side='right').astype(jnp.int32)  # p_k <= p_i
    # errors where i is the preferred-lower side: y_k > y_i among p_k < p_i
    swaps = _counts._prefix_count_greater(ys, lt, ys).astype(jnp.float32)
    # ties in p: pairs with p_k == p_i, y_k > y_i -> half error each.
    ties_gt = (_counts._prefix_count_greater(ys, le, ys)
               - _counts._prefix_count_greater(ys, lt, ys)).astype(jnp.float32)
    # note: prefix [lt, le) == all k with p_k == p_i (including k == i, which
    # contributes 0 since y_i > y_i is false).
    total = jnp.sum(swaps) + 0.5 * jnp.sum(ties_gt)
    return total / n
