"""The bundle QP alone: `solve_bundle_dual_jax` with the estimator's
FISTA iterations (`qp_iters`, 128 by default) over a full bundle, its
slots filled cyclically with the planes of the window's last model,
median ms per call."""

import functools

import jax
import jax.numpy as jnp


def read(ctx):
    from repro.core.qp import solve_bundle_dual_jax
    state = ctx.job.state
    if state is None or int(state.n_active) == 0:
        return None
    K = state.b.shape[0]
    slots = jnp.arange(K) % int(state.n_active)
    A, b = state.A[slots], state.b[slots]
    G = A @ A.T
    mask = jnp.ones((K,), bool)
    est = ctx.job.estimator()
    lam = jnp.asarray(est['lam'], jnp.float32)
    fn = jax.jit(functools.partial(solve_bundle_dual_jax,
                                   n_iter=est.get('qp_iters', 128)))
    return ctx.time_ms(lambda: fn(G, b, lam, mask))
