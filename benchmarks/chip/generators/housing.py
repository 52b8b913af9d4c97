"""Dense housing-like rows: the benchmark's own copy of
`repro.data.synthetic.cadata_like`, with its top utilities tied at a cap."""

from __future__ import annotations

import numpy as np

import gen


def generate(cfg: dict, seed: int) -> gen.Data:
    """Dense low-dimensional rows with a smooth nonlinear utility (the
    `cadata_like` surface), the top `capped_share` of utilities tied at
    one cap value as a price cap ties them."""
    m, n = int(cfg['m']), int(cfg['n'])
    rng = gen.rng(seed, 2)
    X = rng.normal(size=(m, n))
    w = rng.normal(size=n)
    y = (X @ w + 0.5 * np.sin(2.0 * X[:, 0]) * X[:, 1]
         + 0.3 * X[:, 2] ** 2 + cfg['noise'] * rng.normal(size=m))
    n_cap = int(round(cfg['capped_share'] * m))
    if n_cap:
        cap = np.partition(y, m - n_cap)[m - n_cap]
        y = np.minimum(y, cap)
    return gen.Data(X, y)
