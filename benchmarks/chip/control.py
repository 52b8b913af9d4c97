#!/usr/bin/env python3
"""The control of the comparison: the plain reference, put in the
program's place, with its products computed in a lower precision.

The configurations state float32 at JAX's 'highest' matmul precision; the
precision below it is 'high', three bfloat16 passes: each operand x is
split into hi = bf16(x) and lo = bf16(x - hi), and a product x*y becomes
hi*hi' + hi*lo' + lo*hi', summed in float32. The control runs a plain BMRM
(Teo et al.) for as many iterations as the program ran, with every product
of its score matvec, subgradient transpose product, plane offset and
iterate computed so; counting and the bundle dual are the reference's.
It emits the same records as a window and is judged by `check.measure`:
some number has to fail, or the comparison cannot tell the program from a
lower-precision one. 'highest' (float32 products) and 'bfloat16' (one
pass) are there to compare against.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3 \
        [--precision high] [--iters N] [--fault half_rows|loss_altered]

prints one JSON line per seed with the numbers. With
`--program-fault <name>` (faults.py) it runs the cell itself instead, the
program with that fault planted, for `--seconds`, and prints its numbers:
the readings a fault gives at the cell's own size. It runs on whatever
device JAX has (the reference's pair pass runs there).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import check  # noqa: E402
import device  # noqa: E402
import job as job_mod  # noqa: E402
import spec  # noqa: E402

PRECISIONS = ('highest', 'high', 'bfloat16')


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def products(x, y, precision: str) -> np.ndarray:
    """Elementwise x*y as float32, in `precision`."""
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.float32)
    if precision == 'highest':
        return x * y
    xh, yh = _bf16(x), _bf16(y)
    if precision == 'bfloat16':
        return xh * yh
    xl, yl = _bf16(x - xh), _bf16(y - yh)
    return xh * yh + (xh * yl + xl * yh)


class LowPrecision:
    """The reference's products of X, in `precision`, summed in float32."""

    def __init__(self, X, precision: str):
        self.X, self.precision = X, precision
        self.csr = not isinstance(X, np.ndarray)
        if self.csr:
            self.rows = X.row_ids()

    def matvec(self, w) -> np.ndarray:
        w = np.asarray(w, np.float32)
        if self.csr:
            prod = products(self.X.data, w[self.X.indices], self.precision)
            return np.bincount(self.rows, weights=prod,
                               minlength=self.X.shape[0]).astype(np.float32)
        return np.sum(products(self.X, w[None, :], self.precision), axis=1,
                      dtype=np.float32)

    def rmatvec(self, v) -> np.ndarray:
        v = np.asarray(v, np.float32)
        if self.csr:
            prod = products(self.X.data, v[self.rows], self.precision)
            return np.bincount(self.X.indices, weights=prod,
                               minlength=self.X.shape[1]).astype(np.float32)
        return np.sum(products(self.X, v[:, None], self.precision), axis=0,
                      dtype=np.float32)


def dot(x, y, precision: str) -> float:
    return float(np.sum(products(x, y, precision), dtype=np.float32))


FAULTS = ('none', 'half_rows', 'loss_altered')


def bmrm_records(X, y, lam: float, eps: float, iters: int, precision: str,
                 fault: str = 'none') -> list:
    """A plain BMRM run of `iters` iterations from w = 0, as one Record.

    `fault` plants one of the faults the comparison must catch: the loss
    and subgradient of the first half of the rows alone ('half_rows'), or
    each loss value 0.1% off where it is produced ('loss_altered')."""
    import jax.numpy as jnp
    if fault == 'half_rows':
        half = X.shape[0] // 2
        if isinstance(X, np.ndarray):
            X, y = X[:half], y[:half]
        else:
            end = int(X.indptr[half])
            X = type(X)(X.data[:end], X.indices[:end], X.indptr[:half + 1],
                        (half, X.shape[1]))
            y = y[:half]
    ref = check.reference_class('hinge')(X, y)
    low = LowPrecision(X, precision)
    n = X.shape[1]
    w = np.zeros(n, np.float32)
    A, b, S = [], [], []
    best = (np.inf, w)
    alpha = np.ones(1)
    for _ in range(iters):
        p = low.matvec(w)
        c, d, h = ref.pair_pass(jnp.asarray(p))
        loss = float(np.sum(np.asarray(h, np.float64))) / ref.n_pairs
        if fault == 'loss_altered':
            loss *= 1.001
        cd = (np.asarray(c, np.int64) - np.asarray(d, np.int64))
        a = low.rmatvec(cd.astype(np.float32) / np.float32(ref.n_pairs))
        j = loss + lam * dot(w, w, precision)
        if j < best[0]:
            best = (j, w)
        A.append(a)
        b.append(loss - dot(w, a, precision))
        S.append(w)
        alpha, dual = check.dual_max(np.array(A, np.float64), np.array(b),
                                     lam)
        Aa = np.array(A, np.float32)
        w = -np.sum(products(Aa, alpha[:, None].astype(np.float32),
                             precision), axis=0, dtype=np.float32) \
            / np.float32(2.0 * lam)
    return [job_mod.Record(lam=lam, eps=eps,
                           w_best=np.asarray(best[1], np.float64),
                           objective=best[0], A=np.array(A, np.float64),
                           b=np.array(b, np.float64),
                           S=np.array(S, np.float64), alpha=alpha,
                           w=np.asarray(w, np.float64),
                           gap=best[0] - dual)]


def run(cell, seed: int, precision: str, iters: int,
        fault: str = 'none') -> dict:
    t0 = time.perf_counter()
    job = job_mod.make(cell.config, cell.traffic, seed)
    job.generate()
    est = job.estimator()
    recs = bmrm_records(job.data.X, job.data.y, est['lam'], est['eps'],
                        iters, precision, fault)
    for rec in recs:
        rec.problem = job.order[0]
    numbers, _ = check.measure(recs, job.problems,
                               cell.traffic['check_planes'], seed)
    # The control is held to the numbers it gives: it finishes no fit to
    # eps, so it has no stop_gap.
    ok, checks = check.verdict(numbers, {k: v for k, v in cell.limits.items()
                                         if k in numbers})
    return {'workload': cell.name, 'seed': seed, 'precision': precision,
            'fault': fault, 'iters': iters, 'correct': ok, 'numbers': numbers,
            'seconds': time.perf_counter() - t0}


def run_program(cell, seeds, fault: str, seconds: float):
    """The cell itself on each seed, with `fault` (faults.py) planted in
    the program ('none': as it is); yields one result per seed."""
    import contextlib
    import jax
    import faults
    import run as run_mod
    with (contextlib.nullcontext() if fault == 'none'
          else faults.plant(fault)):
        for seed in seeds:
            t0 = time.perf_counter()
            res = run_mod.run_cell(cell, seed, seconds, False,
                                   jax.devices()[:cell.chips])
            yield {'workload': cell.name, 'seed': seed,
                   'program_fault': fault, 'correct': res['correct'],
                   'attempted': res['attempted'],
                   'numbers': res['numbers'],
                   'seconds': time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    ap.add_argument('--precision', choices=PRECISIONS, default='high')
    ap.add_argument('--iters', type=int, default=6)
    ap.add_argument('--fault', choices=FAULTS, default='none')
    ap.add_argument('--program-fault', default=None)
    ap.add_argument('--seconds', type=float, default=3.0)
    ap.add_argument('--max-iter', type=int, default=None,
                    help='with --program-fault: cut each fit to this many '
                    'iterations (a fault that never converges)')
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if args.max_iter is not None:
        cell.traffic.setdefault('estimator', {})['max_iter'] = args.max_iter
    device.setup_compile_cache(spec.repo_root())
    outs = ((run(cell, seed, args.precision, args.iters, args.fault)
             for seed in args.seeds) if args.program_fault is None else
            run_program(cell, args.seeds, args.program_fault, args.seconds))
    for out in outs:
        print(json.dumps(out), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
