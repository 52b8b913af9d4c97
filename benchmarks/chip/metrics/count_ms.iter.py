"""The counting engine alone: `counts_dispatch(p, y, None, engine='tree')`
on the scores of the window's last iterate, median ms per call."""

import jax
import jax.numpy as jnp


def read(ctx):
    from repro.core.counts import counts_dispatch
    X, w = ctx.job.data.X, ctx.job.w
    if w is None:
        return None
    p = jnp.asarray(X.matvec(w) if hasattr(X, 'matvec') else X @ w,
                    jnp.float32)
    y = jnp.asarray(ctx.job.data.y, jnp.float32)
    fn = jax.jit(lambda p, y: counts_dispatch(p, y, None, engine='tree'))
    return ctx.time_ms(lambda: fn(p, y))
