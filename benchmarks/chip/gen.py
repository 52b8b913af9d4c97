"""The data a cell trains on, and the lookup of its generator.

A configuration names its generator; `generators/<name>.py` holds its
`generate(cfg, seed) -> Data`, which returns host arrays: the same seed
gives the same data.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import spec


@dataclasses.dataclass
class Data:
    X: object            # (m, n) float64 ndarray, or a Csr
    y: np.ndarray        # (m,) float64 utilities
    groups: np.ndarray | None = None   # (m,) query ids, or None: one query


@dataclasses.dataclass
class Csr:
    """CSR arrays (canonical: sorted, distinct columns within a row).
    Exposes `data`, `indices`, `indptr`, `shape`, the layout the program's
    CSR adapter reads, and the two products the reference needs."""
    data: np.ndarray     # (nnz,) float64
    indices: np.ndarray  # (nnz,) int32
    indptr: np.ndarray   # (m + 1,) int64
    shape: tuple

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    def row_ids(self) -> np.ndarray:
        return np.repeat(np.arange(self.shape[0], dtype=np.int64),
                         np.diff(self.indptr))

    def matvec(self, w, rows=None) -> np.ndarray:
        rows = self.row_ids() if rows is None else rows
        return np.bincount(rows, weights=self.data * np.asarray(w)[
            self.indices], minlength=self.shape[0])

    def rmatvec(self, v, rows=None) -> np.ndarray:
        rows = self.row_ids() if rows is None else rows
        return np.bincount(self.indices, weights=self.data * np.asarray(v)[
            rows], minlength=self.shape[1])


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of one seed. SeedSequence takes any
    non-negative int, also beyond 64 bits."""
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 64), stream]))


def generate(cfg: dict, seed: int) -> Data:
    """The data of configuration `cfg` for `seed`, by its generator."""
    return spec.load_module('generators', cfg['generator']).generate(
        cfg, seed)
