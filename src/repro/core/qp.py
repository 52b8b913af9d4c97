"""Simplex-constrained dual QP solver for the BMRM master problem.

At BMRM iteration t the master problem (eq. 3) is

    w_t = argmin_w  max_i (<w, a_i> + b_i) + lam * ||w||^2 .

Its dual (Teo et al., 2010, sec. 3) over the t cutting planes is

    max_{alpha in simplex}  D(alpha) = -(1/(4 lam)) alpha' G alpha + b' alpha,
    with  G = A A',  w = -A' alpha / (2 lam).

The paper solves this with CVXOPT; this container is offline so we ship our
own solver: accelerated projected gradient (FISTA) with an exact O(t log t)
Euclidean projection onto the simplex (Duchi et al., 2008). t stays tiny
(tens..hundreds of planes), so this is exact-to-tolerance and costs microseconds.

Two implementations of the same dual:

* `solve_bundle_dual`      — host numpy/float64, adaptive stopping; the
  reference path used by the host BMRM driver.
* `solve_bundle_dual_jax`  — pure traced jax, a fixed iteration count and
  then a tail to a relative Frank-Wolfe gap, active planes selected by a
  boolean mask over a fixed-capacity buffer; designed to run INSIDE the
  device driver's jitted `bundle_step` (DESIGN.md §4), so the whole
  master-problem solve stays on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def project_simplex(v: np.ndarray) -> np.ndarray:
    """Euclidean projection of v onto {x >= 0, sum x = 1} (Duchi et al. 2008)."""
    u = np.sort(v)[::-1]
    css = np.cumsum(u) - 1.0
    rho_idx = np.nonzero(u * np.arange(1, len(v) + 1) > css)[0]
    rho = rho_idx[-1]
    theta = css[rho] / (rho + 1.0)
    return np.maximum(v - theta, 0.0)


def solve_bundle_dual(G: np.ndarray, b: np.ndarray, lam: float,
                      alpha0: np.ndarray | None = None,
                      tol: float = 1e-10, max_iter: int = 5000):
    """Maximize D(alpha) over the simplex; returns (alpha, dual_value).

    f(alpha) = (1/(4 lam)) a'Ga - b'a  is minimized with FISTA; the Lipschitz
    constant of grad f is lmax(G)/(2 lam), computed exactly (G is tiny).
    """
    t = G.shape[0]
    if t == 1:
        return np.ones(1), float(-G[0, 0] / (4.0 * lam) + b[0])
    alpha = (np.ones(t) / t if alpha0 is None
             else project_simplex(np.asarray(alpha0, np.float64)))
    evs = np.linalg.eigvalsh(G)
    L = max(float(evs[-1]) / (2.0 * lam), 1e-12)

    def grad(a):
        return (G @ a) / (2.0 * lam) - b

    def fval(a):
        return float(a @ G @ a / (4.0 * lam) - b @ a)

    z = alpha.copy()
    tk = 1.0
    f_best = fval(alpha)
    a_best = alpha.copy()
    stall = 0
    for it in range(max_iter):
        alpha_new = project_simplex(z - grad(z) / L)
        tk_new = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * tk * tk))
        z = alpha_new + ((tk - 1.0) / tk_new) * (alpha_new - alpha)
        alpha, tk = alpha_new, tk_new
        if it % 10 == 9:  # FISTA is non-monotone: track the best iterate.
            f_cur = fval(alpha)
            if f_cur < f_best - tol * max(1.0, abs(f_best)):
                f_best, a_best, stall = f_cur, alpha.copy(), 0
            else:
                stall += 1
                if stall >= 5:
                    break
    return a_best, -f_best


# ------------------------------------------------- device (traced) variants


def project_simplex_masked(v: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Traced Euclidean projection onto {x >= 0, sum x = 1, x[~mask] = 0}.

    Same Duchi et al. (2008) sort-and-threshold as `project_simplex`, over a
    fixed-capacity vector with inactive slots excluded by pushing them to
    -inf before the sort. Requires at least one True in `mask`.
    """
    k = v.shape[0]
    vm = jnp.where(mask, v, -jnp.inf)
    u = jnp.sort(vm)[::-1]
    css = jnp.cumsum(jnp.where(jnp.isfinite(u), u, 0.0)) - 1.0
    j = jnp.arange(1, k + 1)
    cond = jnp.isfinite(u) & (u * j.astype(v.dtype) > css)
    rho = jnp.max(jnp.where(cond, j, 1))
    theta = jnp.take(css, rho - 1) / rho.astype(v.dtype)
    return jnp.where(mask, jnp.maximum(v - theta, 0.0), 0.0)


# The device QP's accuracy: after its `n_iter` fixed steps FISTA goes on,
# up to `_QP_MAX_ROUNDS * n_iter` steps in all, while the dual value may
# lie more than `_QP_RTOL` of the dual's own terms below its maximum.
_QP_RTOL = 1e-3
_QP_MAX_ROUNDS = 32


def solve_bundle_dual_jax(G: jnp.ndarray, b: jnp.ndarray, lam,
                          mask: jnp.ndarray,
                          alpha0: jnp.ndarray | None = None,
                          n_iter: int = 256):
    """Masked FISTA for the bundle dual, run to a Frank-Wolfe gap, fully
    traceable.

    G is the (K, K) Gram buffer and b the (K,) offset buffer of the device
    driver's fixed-capacity bundle state; `mask` selects the active planes
    (rows/cols outside it are ignored). Runs `n_iter` FISTA steps, then
    more (a `while_loop`, so one compiled program still serves every BMRM
    iteration) while the best iterate's Frank-Wolfe gap, which bounds how
    far its dual value lies below the maximum, exceeds `_QP_RTOL` of the
    dual's two terms: on ill-conditioned bundles (RCV1 at lam = 1e-5) 128
    steps left D(alpha) up to 3% of J short after 17 BMRM iterations.
    Returns (alpha, dual_value) with alpha zero outside `mask`. The
    Lipschitz constant uses the Gershgorin row-sum bound (exact
    eigen-decomposition is host-only); FISTA being non-monotone, the best
    iterate seen is tracked and returned.
    """
    dt = G.dtype
    lam = jnp.asarray(lam, dt)
    mask_f = mask.astype(dt)
    Gm = G * mask_f[:, None] * mask_f[None, :]
    bm = jnp.where(mask, b, 0.0).astype(dt)
    # lmax(Gm) by a few power iterations (Gershgorin alone is up to K times
    # too big, which shrinks the FISTA step and starves convergence within
    # the fixed budget). Power iteration approaches lmax from below, so pad
    # by 10% and clamp to the always-safe Gershgorin bound; an
    # underestimate merely slows FISTA — the caller's dual-value gap
    # statistic stays valid for ANY feasible iterate.
    gersh = jnp.max(jnp.sum(jnp.abs(Gm), axis=1))

    def _pow(_, v):
        u = Gm @ v
        return u / jnp.maximum(jnp.linalg.norm(u), 1e-30)

    v = jax.lax.fori_loop(0, 12, _pow, mask_f / jnp.maximum(
        jnp.linalg.norm(mask_f), 1e-30))
    lmax = jnp.minimum(1.1 * (v @ (Gm @ v)), gersh)
    L = jnp.maximum(lmax / (2.0 * lam), jnp.asarray(1e-12, dt))

    def grad(a):
        return (Gm @ a) / (2.0 * lam) - bm

    def fval(a):
        return a @ Gm @ a / (4.0 * lam) - bm @ a

    alpha = (project_simplex_masked(jnp.zeros_like(bm), mask)
             if alpha0 is None else project_simplex_masked(alpha0, mask))

    def body(_, carry):
        alpha, z, tk, a_best, f_best = carry
        alpha_new = project_simplex_masked(z - grad(z) / L, mask)
        tk_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        z = alpha_new + ((tk - 1.0) / tk_new) * (alpha_new - alpha)
        f_new = fval(alpha_new)
        better = f_new < f_best
        a_best = jnp.where(better, alpha_new, a_best)
        f_best = jnp.where(better, f_new, f_best)
        return alpha_new, z, tk_new, a_best, f_best

    init = (alpha, alpha, jnp.asarray(1.0, dt), alpha, fval(alpha))
    carry = jax.lax.fori_loop(0, n_iter, body, init)

    def unconverged(c):
        k, (_, _, _, a, _) = c
        g = grad(a)
        # Frank-Wolfe gap: an upper bound on fval(a) - min fval
        fw_gap = g @ a - jnp.min(jnp.where(mask, g, jnp.inf))
        scale = jnp.abs(bm @ a) + a @ Gm @ a / (4.0 * lam)
        return (k < _QP_MAX_ROUNDS * n_iter) & (fw_gap > _QP_RTOL * scale)

    _, (_, _, _, a_best, f_best) = jax.lax.while_loop(
        unconverged, lambda c: (c[0] + 1, body(c[0], c[1])), (n_iter, carry))
    return a_best, -f_best
