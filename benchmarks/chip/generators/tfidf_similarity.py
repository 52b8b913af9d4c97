"""Variable-length tf-idf rows and utilities of similarity to a held-out
document: the benchmark's own copy of the ideas in
`repro.data.sparse.random_tfidf` and `repro.data.synthetic.reuters_like`,
with rows of varying length (real tf-idf rows are), so the program takes
its non-uniform CSR branch."""

from __future__ import annotations

import numpy as np

import gen


def _lengths(rng, cfg: dict, rows: int, total: int) -> np.ndarray:
    """Lognormal row lengths (median `row_len_median`, log-sd
    `row_len_sigma`, within [1, `row_len_max`]) scaled to sum to `total`
    exactly, so every seed gives arrays of one size."""
    hi = int(cfg['row_len_max'])
    if not rows <= total <= rows * hi:
        raise ValueError(f'{total} entries cannot fill {rows} rows of 1 to '
                         f'{hi} entries')
    raw = np.clip(rng.lognormal(np.log(cfg['row_len_median']),
                                cfg['row_len_sigma'], rows), 1, hi)
    lens = np.clip(np.floor(raw * total / raw.sum()), 1, hi).astype(np.int64)
    diff = total - int(lens.sum())
    while diff:
        ok = np.nonzero(lens < hi if diff > 0 else lens > 1)[0]
        pick = rng.choice(ok, size=min(abs(diff), ok.size), replace=False)
        lens[pick] += 1 if diff > 0 else -1
        diff = total - int(lens.sum())
    return lens


def _distinct_columns(rng, lens: np.ndarray, n: int) -> np.ndarray:
    """Sorted keys row * 2**cbits + col: row i gets lens[i] distinct columns.

    Columns are drawn log-uniformly over ranks (P(rank r) ~ 1/(r ln n),
    Zipf-like), more than a row needs; duplicates merge, and lens[i] of the
    row's distinct columns are kept at random (a row short of distinct
    columns draws again)."""
    rows_total = lens.size
    cbits, rbits = int(n - 1).bit_length(), int(rows_total - 1).bit_length()
    ubits = 62 - cbits - rbits
    if ubits < 16:
        raise ValueError('too many rows or columns for one int64 sort key')
    draws = np.ceil(1.25 * lens).astype(np.int64) + 4
    keys = np.empty(0, np.int64)
    need = np.arange(rows_total)
    log_n = np.float32(np.log(n + 1.0))
    while True:
        u = rng.random(int(draws[need].sum()), dtype=np.float32)
        u *= log_n
        np.exp(u, out=u)
        cols = np.minimum(u.astype(np.int64) - 1, n - 1)
        cols |= np.repeat(need << cbits, draws[need])
        keys = np.concatenate([keys, cols])
        keys.sort()
        keys = keys[np.concatenate([[True], keys[1:] != keys[:-1]])]
        have = np.bincount(keys >> cbits, minlength=rows_total)
        need = np.nonzero(have < lens)[0]
        if not need.size:
            break
        draws[need] *= 2
    # One sort by (row, random priority) ranks each row's columns.
    col_mask = (1 << cbits) - 1
    rows = keys >> cbits
    keys = (rows << (ubits + cbits)) | (keys & col_mask) | (
        rng.integers(0, 1 << ubits, keys.size, dtype=np.int64) << cbits)
    keys.sort()
    rows = keys >> (ubits + cbits)
    start = np.zeros(rows_total + 1, np.int64)
    np.cumsum(have, out=start[1:])
    keep = np.arange(keys.size) - start[rows] < lens[rows]
    keys = (rows[keep] << cbits) | (keys[keep] & col_mask)
    keys.sort()
    return keys, cbits


def generate(cfg: dict, seed: int) -> gen.Data:
    """Variable-length tf-idf rows and similarity-to-target utilities.

    The m rows hold `nnz` distinct (row, column) entries in all, whatever
    the seed; their lengths follow `_lengths`, their columns a Zipf-like
    popularity (`_distinct_columns`); values are lognormal and rows are
    L2-normalised. One extra row is drawn and held out as the target:
    y_i = <x_i, target>, so nearly every utility is distinct.
    """
    m, n = int(cfg['m']), int(cfg['n'])
    rng = gen.rng(seed, 1)
    lens = np.append(_lengths(rng, cfg, m, int(cfg['nnz'])),
                     _lengths(rng, cfg, 1, int(cfg['row_len_median'])))
    keys, cbits = _distinct_columns(rng, lens, n)
    rows = keys >> cbits
    indices = (keys & ((1 << cbits) - 1)).astype(np.int32)
    data = rng.lognormal(0.0, 0.5, keys.size)
    data /= np.sqrt(np.bincount(rows, weights=data * data))[rows]
    indptr = np.zeros(m + 2, np.int64)
    np.cumsum(lens, out=indptr[1:])
    # The last row is the held-out target document.
    t0 = int(indptr[m])
    target = np.zeros(n)
    target[indices[t0:]] = data[t0:]
    X = gen.Csr(data[:t0], indices[:t0], indptr[:m + 1], (m, n))
    return gen.Data(X, X.matvec(target, rows[:t0]))
