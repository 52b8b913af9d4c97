"""The plain reference of the pairwise hinge loss (`loss='hinge'`), which
the benchmark checks the program against.

It imports nothing of the program. The loss of Airola et al. (eq. 4),
its frequency vectors (eqs. 5, 6) and the Lemma 2 subgradient are
computed straight from their definitions, over all O(m^2) ordered pairs,
in row blocks on the device. With query ids g, pairs form within a query
only (j ~ i: g_j = g_i; without them every j):

    c_i = |{j ~ i : y_i < y_j, p_j < p_i + 1}|
    d_i = |{j ~ i : y_i > y_j, p_j > p_i - 1}|
    R(w) = (1/N) sum_{j ~ i, y_i < y_j} max(0, 1 + p_i - p_j)
    a(w) = (1/N) X^T (c - d)

Scores p = X w and the transpose product are float64 on the host; the
pair comparisons and per-row hinge sums are float32 on the device (the
only float type the device computes natively).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

ROW_BLOCK = 256


@functools.partial(jax.jit, static_argnames=('block',))
def _pair_pass(p, y, g, block: int):
    """(c, d, per-row hinge sums); g holds query ids (all 0: one query)."""
    m = p.shape[0]
    nb = -(-m // block)
    pad = nb * block - m
    pb_all = jnp.pad(p, (0, pad)).reshape(nb, block)
    yb_all = jnp.pad(y, (0, pad)).reshape(nb, block)
    gb_all = jnp.pad(g, (0, pad), constant_values=-1).reshape(nb, block)

    def one(args):
        pb, yb, gb = args
        same = g[None, :] == gb[:, None]
        higher = same & (y[None, :] > yb[:, None])
        lower = same & (y[None, :] < yb[:, None])
        c = jnp.sum(higher & (p[None, :] < pb[:, None] + 1.0), axis=1,
                    dtype=jnp.int32)
        d = jnp.sum(lower & (p[None, :] > pb[:, None] - 1.0), axis=1,
                    dtype=jnp.int32)
        h = jnp.sum(jnp.where(higher, jnp.maximum(
            1.0 + pb[:, None] - p[None, :], 0.0), 0.0), axis=1)
        return c, d, h

    c, d, h = jax.lax.map(one, (pb_all, yb_all, gb_all))
    return c.reshape(-1)[:m], d.reshape(-1)[:m], h.reshape(-1)[:m]


def num_pairs(y: np.ndarray, groups: np.ndarray | None = None) -> int:
    """N = |{(i, j) : j ~ i, y_i < y_j}|, exact."""
    y = np.asarray(y)
    if groups is None:
        per_group = np.array([y.size])
        _, per_tie = np.unique(y, return_counts=True)
    else:
        g = np.asarray(groups)
        _, per_group = np.unique(g, return_counts=True)
        _, per_tie = np.unique(np.stack([g, y]), axis=1, return_counts=True)
    return (int(np.sum(per_group.astype(np.int64) ** 2))
            - int(np.sum(per_tie.astype(np.int64) ** 2))) // 2


class Reference:
    """Loss and subgradient of the pairwise hinge at any w, for one data
    set. `matvec(w)` and `rmatvec(v)` are float64 host products of X."""

    def __init__(self, X, y, groups=None, block: int = ROW_BLOCK):
        self.X = X
        self.y = np.asarray(y, np.float64)
        self.n_pairs = num_pairs(self.y, groups)
        self._y_dev = jnp.asarray(self.y, jnp.float32)
        g = np.zeros(self.y.shape, np.int32) if groups is None else \
            np.unique(np.asarray(groups), return_inverse=True)[1]
        self._g_dev = jnp.asarray(g, jnp.int32)
        self._block = int(block)
        self._last = None
        if isinstance(X, np.ndarray):
            self._rows = None
        else:
            self._rows = X.row_ids()

    def matvec(self, w) -> np.ndarray:
        w = np.asarray(w, np.float64)
        if self._rows is None:
            return self.X @ w
        return self.X.matvec(w, self._rows)

    def rmatvec(self, v) -> np.ndarray:
        if self._rows is None:
            return self.X.T @ v
        return self.X.rmatvec(v, self._rows)

    def loss_and_subgrad(self, w):
        """(R(w), a(w)); the last point asked for is remembered."""
        w = np.asarray(w, np.float64)
        if self._last is not None and np.array_equal(self._last[0], w):
            return self._last[1]
        self._last = (w.copy(), self._evaluate(w))
        return self._last[1]

    def pair_pass(self, p_dev):
        """(c, d, per-row hinge sums) at float32 scores on the device."""
        return _pair_pass(p_dev, self._y_dev, self._g_dev, self._block)

    def _evaluate(self, w):
        p = self.matvec(w)
        c, d, h = self.pair_pass(jnp.asarray(p, jnp.float32))
        c = np.asarray(c, np.int64)
        d = np.asarray(d, np.int64)
        loss = float(np.sum(np.asarray(h, np.float64))) / self.n_pairs
        a = self.rmatvec((c - d).astype(np.float64)) / self.n_pairs
        return loss, a

