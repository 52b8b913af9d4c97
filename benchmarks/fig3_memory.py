"""Paper Figure 3: peak memory vs m. TreeRSVM and the blocked PairRSVM are
both O(ms); the paper's PRSVM baseline is O(ms + m^2) because it
materializes the pairwise expansion. We measure our two methods plus a
simulated PRSVM-style pair materialization to reproduce the blow-up."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import counts as C
from repro.data import reuters_like

from .common import Reporter, peak_rss_mb


def _pair_expansion_bytes(y: np.ndarray) -> float:
    """PRSVM's memory model: 2 entries (8 B indices + values) per preference
    pair — computed analytically (actually materializing it would OOM)."""
    n_pairs = C.num_pairs_host(y)
    return 2 * 8.0 * n_pairs


def main(full: bool = False):
    rep = Reporter('fig3_memory',
                   ['m', 'data_mb', 'tree_peak_mb', 'prsvm_pairs_mb'])
    sizes = [1000, 4000, 16000] + ([65536] if full else [32768])
    reu = reuters_like(m=max(sizes), m_test=10, n=49152, nnz_per_row=50)
    for m in sizes:
        Xm = reu.X.rows(m)
        y = reu.y[:m]
        data_mb = (Xm.data.nbytes + Xm.indices.nbytes
                   + Xm.indptr.nbytes) / 1e6
        base = peak_rss_mb()
        c, d = C.counts_fused(jnp.asarray(Xm.matvec(np.ones(Xm.shape[1])),
                                    jnp.float32), jnp.asarray(y, jnp.float32))
        c.block_until_ready()
        peak = peak_rss_mb()
        rep.row(m, round(data_mb, 1), round(max(peak, base), 1),
                round(_pair_expansion_bytes(y) / 1e6, 1))
    return rep


if __name__ == '__main__':
    import sys
    main(full='--full' in sys.argv).save()
