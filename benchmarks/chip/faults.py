"""Faults planted in the program underneath a whole run, each one a
change a later version of the timed path could make. The comparison that
decides `correct` (check.py) has to catch every one the cell can have:
`tests/test_correct.py` runs them at a CPU size, and
`control.py --program-fault` on the chip at the cell's own size.

Each fault replaces one function of the program for the length of a
`with plant(name):` block, and clears the compiled chunks before and
after, so that the fault is traced in and then out again.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def _state_unchanged(bmrm_mod, oracle_mod):
    """A step that returns its state unchanged."""
    orig = bmrm_mod._bundle_step

    def step(s, step_fn, lam, eps, qp_iters):
        _, r = orig(s, step_fn, lam, eps, qp_iters)
        return s, r
    return bmrm_mod, '_bundle_step', step


def _half_the_rows(bmrm_mod, oracle_mod):
    """Loss and subgradient of the first half of the rows, the mean taken
    over those alone."""
    orig = oracle_mod._loss_and_coeffs

    def half(p, y, g, inv_n, v=None, **kw):
        m = p.shape[0]
        k = m // 2
        loss, cd = orig(p[:k], y[:k], None if g is None else g[:k],
                        inv_n * 4.0, v, **kw)
        return loss, jnp.concatenate([cd, jnp.zeros((m - k,), cd.dtype)])
    return oracle_mod, '_loss_and_coeffs', half


def _loss_altered(bmrm_mod, oracle_mod):
    """Each loss 0.1% off where it is produced."""
    orig = oracle_mod._loss_and_coeffs

    def altered(*a, **kw):
        loss, cd = orig(*a, **kw)
        return loss * 1.001, cd
    return oracle_mod, '_loss_and_coeffs', altered


def _iterate_altered(bmrm_mod, oracle_mod):
    """Each new iterate 0.1% off."""
    orig = bmrm_mod._bundle_step

    def step(s, step_fn, lam, eps, qp_iters):
        s2, r = orig(s, step_fn, lam, eps, qp_iters)
        return s2._replace(w=s2.w * 1.001), r
    return bmrm_mod, '_bundle_step', step


def _qp_uniform(bmrm_mod, oracle_mod):
    """The QP skipped: alpha uniform over the active planes, with its own
    (true) dual value."""
    def uniform(G, b, lam, mask, alpha0=None, n_iter=0):
        m = mask.astype(b.dtype)
        alpha = m / jnp.sum(m)
        return alpha, b @ alpha - alpha @ G @ alpha / (4.0 * lam)
    return bmrm_mod, 'solve_bundle_dual_jax', uniform


def _alpha_off_simplex(bmrm_mod, oracle_mod):
    """The dual's weights 0.1% too heavy (off the simplex), with the true
    dual value of those weights."""
    orig = bmrm_mod.solve_bundle_dual_jax

    def heavy(G, b, lam, mask, **kw):
        alpha, _ = orig(G, b, lam, mask, **kw)
        alpha = alpha * 1.001
        return alpha, b @ alpha - alpha @ G @ alpha / (4.0 * lam)
    return bmrm_mod, 'solve_bundle_dual_jax', heavy


def _dual_inflated(bmrm_mod, oracle_mod):
    """The dual value reported 0.1% too high, so fits stop early."""
    orig = bmrm_mod.solve_bundle_dual_jax

    def inflated(*a, **kw):
        alpha, dual = orig(*a, **kw)
        return alpha, dual + 1e-3 * jnp.abs(dual)
    return bmrm_mod, 'solve_bundle_dual_jax', inflated


def _eps_loose(bmrm_mod, oracle_mod):
    """A fit stopped once its gap is under 4 eps."""
    orig = bmrm_mod._bundle_step

    def step(s, step_fn, lam, eps, qp_iters):
        return orig(s, step_fn, lam, 4.0 * eps, qp_iters)
    return bmrm_mod, '_bundle_step', step


FAULTS = {'state_unchanged': _state_unchanged,
          'half_the_rows': _half_the_rows,
          'loss_altered': _loss_altered,
          'iterate_altered': _iterate_altered,
          'qp_uniform': _qp_uniform,
          'alpha_off_simplex': _alpha_off_simplex,
          'dual_inflated': _dual_inflated,
          'eps_loose': _eps_loose}


def _clear(bmrm_mod):
    bmrm_mod._SHARED_CHUNKS.clear()
    jax.clear_caches()


@contextlib.contextmanager
def plant(name: str):
    from repro.core import bmrm as bmrm_mod
    from repro.core import oracle as oracle_mod
    mod, attr, fn = FAULTS[name](bmrm_mod, oracle_mod)
    orig = getattr(mod, attr)
    _clear(bmrm_mod)
    setattr(mod, attr, fn)
    try:
        yield
    finally:
        setattr(mod, attr, orig)
        _clear(bmrm_mod)
