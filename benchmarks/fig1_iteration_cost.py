"""Paper Figure 1: average per-iteration subgradient+loss cost vs m.

TreeRSVM's oracle is O(ms + m log m); PairRSVM's is O(ms + m^2). The paper
shows the curves separating by orders of magnitude past ~10^4 examples
(their 512k Reuters point: 7 s vs 2760 s). We reproduce the shape on the
same two dataset archetypes (dense cadata-like, sparse reuters-like).

Post-refactor this also measures the oracle layer itself: `tree_s` is the
device-resident `core.oracle.TreeOracle` (one fused jitted step: matvec +
counts + loss + subgradient), `tree_host_s` is the pre-refactor
estimator loop it replaced (host numpy matvecs, device counts, c/d
round-tripped through the host as float64). The acceptance bar for the
refactor: tree_s <= tree_host_s at m >= 1e5 on the same hardware.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import counts as C
from repro.core.oracle import make_oracle
from repro.data import cadata_like, reuters_like

from .common import Reporter, timeit


def _w_for(X, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).normal(size=X.shape[1])


def _host_oracle_seconds(X, y, method: str, block: int = 2048) -> float:
    """The seed estimator's host loop, kept verbatim as the baseline:
    numpy matvec -> device counts -> counts back to host float64 -> numpy
    transpose-matvec."""
    w = _w_for(X)
    yj = jnp.asarray(y, jnp.float32)

    def oracle():
        p = X.matvec(w) if hasattr(X, 'matvec') else X @ w
        pj = jnp.asarray(p, jnp.float32)
        if method == 'tree':
            c, d = C.counts_fused(pj, yj)
        else:
            c, d = C.counts_blocked_host(pj, yj, block=block)
        cd = np.asarray(c, np.float64) - np.asarray(d, np.float64)
        if hasattr(X, 'rmatvec'):
            return X.rmatvec(cd)
        return X.T @ cd

    return timeit(oracle, repeats=3, warmup=1)


def _oracle_layer_seconds(X, y, method: str) -> float:
    """One full loss_and_subgrad through the RankOracle layer."""
    orc = make_oracle(X, y, method=method)
    w = _w_for(X)

    def oracle():
        loss, a = orc.loss_and_subgrad(w)
        return float(loss), np.asarray(a)    # force completion

    return timeit(oracle, repeats=3, warmup=1)


def main(full: bool = False):
    rep = Reporter('fig1_iteration_cost',
                   ['dataset', 'm', 'tree_s', 'tree_host_s', 'pairs_s',
                    'host_over_dev', 'pairs_over_tree'])
    # each archetype gets a >= 1e5 point (the device-vs-host acceptance bar)
    sizes_cad = [1000, 2000, 4000, 8000, 16000, 131072]
    sizes_reu = [1000, 4000, 16000, 32768, 131072] + ([262144] if full else [])
    pairs_cap = 262144 if full else 32768

    def fmt(v):
        return round(v, 4) if np.isfinite(v) else ''

    cad = cadata_like(m=max(sizes_cad), m_test=10)
    for m in sizes_cad:
        t = _oracle_layer_seconds(cad.X[:m], cad.y[:m], 'tree')
        th = _host_oracle_seconds(cad.X[:m], cad.y[:m], 'tree')
        p = (_oracle_layer_seconds(cad.X[:m], cad.y[:m], 'pairs')
             if m <= pairs_cap else float('nan'))
        rep.row('cadata', m, fmt(t), fmt(th), fmt(p),
                round(th / t, 2), fmt(p / t) and round(p / t, 1))

    reu = reuters_like(m=max(sizes_reu), m_test=10, n=49152, nnz_per_row=50)
    for m in sizes_reu:
        Xm = reu.X.rows(m)
        ym = reu.y[:m]
        t = _oracle_layer_seconds(Xm, ym, 'tree')
        th = _host_oracle_seconds(Xm, ym, 'tree')
        # O(m^2) pass gets expensive: skip pairs beyond the cap
        p = (_oracle_layer_seconds(Xm, ym, 'pairs')
             if m <= pairs_cap else float('nan'))
        rep.row('reuters', m, fmt(t), fmt(th), fmt(p),
                round(th / t, 2), fmt(p / t) and round(p / t, 1))
    return rep


if __name__ == '__main__':
    import sys
    main(full='--full' in sys.argv).save()
