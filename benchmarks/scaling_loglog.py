"""Beyond-paper: empirical complexity exponents.

Fits log(time) ~ a + b log(m) for the tree oracle and the pairwise oracle.
Theorem 2 predicts b ~= 1 for TreeRSVM (the m log m term is dominated by the
O(ms) matvec at Reuters sparsity) and b ~= 2 for PairRSVM."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import counts as C

from .common import Reporter, timeit


def _counts_seconds(m: int, method: str, seed: int = 0) -> float:
    rng = np.random.default_rng(seed)
    p = jnp.asarray(rng.normal(size=m).astype(np.float32))
    y = jnp.asarray(rng.normal(size=m).astype(np.float32))

    if method == 'tree':
        fn = lambda: C.counts_fused(p, y)[0].block_until_ready()
    else:
        fn = lambda: C.counts_blocked_host(p, y)[0].block_until_ready()
    return timeit(fn, repeats=3, warmup=1)


def main(full: bool = False):
    rep = Reporter('scaling_loglog', ['method', 'm', 'seconds'])
    tree_sizes = [4096, 16384, 65536, 262144] + ([1048576] if full else [])
    pair_sizes = [4096, 16384, 65536] + ([131072] if full else [])
    logs = {}
    for method, sizes in (('tree', tree_sizes), ('pairs', pair_sizes)):
        xs, ys = [], []
        for m in sizes:
            s = _counts_seconds(m, method)
            rep.row(method, m, round(s, 5))
            xs.append(np.log(m))
            ys.append(np.log(s))
        b = np.polyfit(xs, ys, 1)[0]
        logs[method] = b
        rep.row(method, 'exponent', round(b, 3))
    print(f"[scaling_loglog] fitted exponents: tree={logs['tree']:.2f} "
          f"(theory ~1), pairs={logs['pairs']:.2f} (theory ~2)")
    return rep


if __name__ == '__main__':
    import sys
    main(full='--full' in sys.argv).save()
