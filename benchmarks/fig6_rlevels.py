"""Beyond-paper: the r-dependence the paper removes, measured directly.

Joachims (2006) / SVM^rank computes the counts in O(ms + m log m + rm);
this paper's tree method costs O(ms + m log m) independent of r. We sweep
the number of distinct utility levels r at fixed m and time both oracles:
the r-level baseline grows linearly in r, the tree stays flat — at r = m
(the real-valued-utilities regime of the paper's experiments) the baseline
has degraded to quadratic."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import counts as C
from repro.core import joachims as J

from .common import Reporter, timeit


def main(full: bool = False):
    m = 65536 if full else 16384
    rep = Reporter('fig6_rlevels', ['m', 'r', 'rlevel_s', 'tree_s'])
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.normal(size=m).astype(np.float32))
    rs = [2, 8, 32, 128, 512] + ([2048] if full else [1024])
    for r in rs:
        yl = jnp.asarray(rng.integers(0, r, size=m).astype(np.int32))
        yv = yl.astype(jnp.float32)
        t_r = timeit(lambda: J.counts_rlevel(p, yl, r)[0].block_until_ready())
        t_t = timeit(lambda: C.counts_fused(p, yv)[0].block_until_ready())
        rep.row(m, r, round(t_r, 5), round(t_t, 5))
    return rep


if __name__ == '__main__':
    import sys
    main(full='--full' in sys.argv).save()
