#!/usr/bin/env python3
"""Smoke run of the RankSVM system's main path on one TPU chip.

    python chip_smoke.py [--seed N]            # one chip, phases 1-6
    python chip_smoke.py --four-chips [--seed N]

One process, no subprocesses. Phases, in order:

  1. device   platform, device_kind and device count; anything but a TPU
              exits non-zero (there is no CPU fallback).
  2. cache    the persistent compile cache: JAX_COMPILATION_CACHE_DIR if
              set, else <repo>/.jax_cache. Every phase prints its compile
              seconds, so a second run shows the cache at work.
  3. train    the `ranksvm-linear` configuration (configs/ranksvm_paper.py:
              m = 2^20, n = 49152, lam = 1e-5) on `reuters_like` CSR data,
              `RankSVM(method='tree', solver='device')` for 10 BMRM
              iterations; then the tree oracle's counts, loss and
              subgradient at the fitted w against `core/ref.py` on m = 4096
              rows of the same generator.
  4. kernels  `rank_counts` (m = 2^20, graded 5-level utilities) and
              `pairwise_counts` (m = 4096) compiled for the chip, bit-exact
              against `counts_fused`; then a `RankSVM(method='auto')` fit at
              m = 4096, whose `counts_auto` runs the pairwise kernel.
  5. serve    a `RankingService` over the phase-3 weights answers top-k
              requests of 64-512 dense candidates at n = 49152, checked
              against a stable NumPy argsort of X @ w.
  6. result   the last stdout line is one JSON object:
              {"ok": true, "device": {"platform", "kind", "count"}}.

`--four-chips` runs only the sharded path instead: the phase-3 data on a
(4, 1) ('data', 'model') mesh from `launch/mesh.py` through
`RankSVM(method='sharded')` (CSR slot layout), against the one-device tree
fit on the same data. Any failed check raises, so the script exits
non-zero and prints no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, 'src'))

ITERS = 10              # BMRM iterations of every fit
SYNC = 5                # device-driver steps per host sync (divides ITERS)
BLOCKED_M = 4096        # size of the O(m^2) reference checks
KERNEL_M = 1 << 20      # rank-counts kernel size
SERVE_POOL = 2048       # held-out rows that become serving candidates
SERVE_SIZES = (64, 200, 333, 512)
SERVE_K = 10
# The event JAX records around each backend compile (or cache lookup).
BACKEND_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's backend-compile events (a persistent-cache hit records
    only its retrieval time), so each phase can report its compile
    seconds."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


def setup_compile_cache() -> str:
    """JAX's persistent compile cache: the directory that
    JAX_COMPILATION_CACHE_DIR names (JAX reads it itself), else one fixed
    path inside the checkout, so a later run finds what this one wrote."""
    cache = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not cache:
        cache = os.path.join(REPO, '.jax_cache')
        jax.config.update('jax_compilation_cache_dir', cache)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    return cache


def run_phase(name: str, clock: CompileClock, fn, *args, **kwargs):
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn(*args, **kwargs)
    print(f'[{name}] ok: {time.perf_counter() - t0:.1f} s wall, '
          f'{clock.seconds - c0:.1f} s compile', flush=True)
    return out


def paper_data(seed: int, m: int | None = None, m_test: int = 0):
    """`reuters_like` at the ranksvm-linear width (m defaults to the
    configuration's): CSR, 50 nonzeros per row, similarity-to-target
    utilities."""
    from repro.configs.ranksvm_paper import config
    from repro.data import reuters_like
    cfg = config()
    return cfg, reuters_like(m=m or cfg.n_examples, m_test=m_test,
                             n=cfg.n_features, nnz_per_row=50, seed=seed)


def fit(X, y, lam: float, method: str, iters: int = ITERS, **kw):
    from repro.core.ranksvm import RankSVM
    svm = RankSVM(lam=lam, method=method, solver='device', max_iter=iters,
                  sync_every=SYNC, **kw)
    return svm.fit(X, y)


def check_fit(svm, label: str, iters: int = ITERS) -> None:
    rep = svm.report_
    hist = np.asarray(rep.loss_history)
    print(f'  {label}: {rep.iterations} iterations, objective '
          f'{rep.objective!r}, first/last loss {hist[0]!r}/{hist[-1]!r}, '
          f'{rep.seconds:.2f} s')
    check(rep.solver == 'device', f'{label}: solver {rep.solver!r}')
    check(rep.iterations == iters, f'{label}: {rep.iterations} iterations')
    check(np.all(np.isfinite(hist)) and np.isfinite(rep.objective),
          f'{label}: non-finite objective or loss')
    # w0 = 0, so J(w0) = R_emp(0) bounds the best objective from above.
    check(rep.objective <= hist[0], f'{label}: best objective went up')


# ------------------------------------------------------------------ phases


def phase_train(seed: int):
    cfg, data = paper_data(seed, m_test=SERVE_POOL)
    r = np.unique(data.y).size
    print(f'  data: m={data.m} n={data.n} nnz={data.X.nnz} distinct '
          f'utilities r={r}')
    svm = fit(data.X, data.y, cfg.lam, 'tree')
    check_fit(svm, 'tree fit')
    # The reference check and the service need a fitted w away from w0 = 0.
    check(np.any(svm.w_ != 0), 'the best iterate is still w0 = 0')
    feats = svm.oracle_._feats
    check(feats.kind == 'csr' and feats.device_rmatvec,
          'the CSR oracle did not take the device rmatvec')
    return cfg, data, svm


def phase_reference(seed: int, w: np.ndarray):
    """Tree oracle vs the O(m^2) reference at the fitted w, m = 4096."""
    from repro.core import ref as R
    from repro.core.counts import counts_fused
    from repro.core.oracle import make_oracle
    _, small = paper_data(seed, BLOCKED_M)
    oracle = make_oracle(small.X, small.y, method='tree')
    loss, a = oracle.loss_and_subgrad(w)
    a = np.asarray(a, np.float64)
    y = jnp.asarray(small.y, jnp.float32)
    with jax.default_matmul_precision('float32'):
        X = jnp.asarray(small.X.to_dense(), jnp.float32)
        p = X @ jnp.asarray(w, jnp.float32)
        c, d = counts_fused(p, y)
        cr, dr = R.counts_ref(p, y)
        loss_r = float(R.loss_ref(p, y))
        a_r = np.asarray(R.subgradient_ref(X, p, y), np.float64)
    check(np.array_equal(np.asarray(c), np.asarray(cr))
          and np.array_equal(np.asarray(d), np.asarray(dr)),
          'tree counts differ from counts_ref')
    rel_loss = abs(float(loss) - loss_r) / abs(loss_r)
    rel_a = np.linalg.norm(a - a_r) / np.linalg.norm(a_r)
    print(f'  m={BLOCKED_M}: counts exact, loss {float(loss)!r} vs ref '
          f'{loss_r!r} (rel {rel_loss:.2e}), subgradient rel err '
          f'{rel_a:.2e}')
    check(rel_loss <= 1e-4, 'loss differs from loss_ref')
    check(rel_a <= 1e-3, 'subgradient differs from subgradient_ref')


def phase_kernels(seed: int):
    from repro.core.counts import counts_fused
    from repro.data import ordinal_like
    from repro.kernels.pairwise_rank import ops as pw_ops
    from repro.kernels.rank_counts import ops as rc_ops
    data = ordinal_like(m=KERNEL_M, m_test=0, n=32, levels=5, seed=seed)
    w = np.random.default_rng(seed).normal(size=data.n)
    p = jnp.asarray(data.X @ w, jnp.float32)
    y = jnp.asarray(data.y, jnp.float32)
    levels = np.unique(data.y).size
    branch = ('kernel' if levels <= rc_ops.DEFAULT_LEVELS
              else 'tree fallback')
    print(f'  rank_counts m={KERNEL_M}: {levels} utility levels, '
          f'capacity {rc_ops.DEFAULT_LEVELS} -> {branch} branch')
    check(branch == 'kernel', 'rank_counts inputs take the tree fallback')
    for name, fn, m in (
            ('rank_counts', rc_ops.rank_counts, KERNEL_M),
            ('pairwise_counts', pw_ops.pairwise_counts, BLOCKED_M)):
        c, d = jax.block_until_ready(fn(p[:m], y[:m], interpret=False))
        cf, df = counts_fused(p[:m], y[:m])
        check(np.array_equal(np.asarray(c), np.asarray(cf))
              and np.array_equal(np.asarray(d), np.asarray(df)),
              f'{name} differs from counts_fused at m={m}')
        print(f'  {name} m={m}: bit-exact against counts_fused')

    small = ordinal_like(m=BLOCKED_M, m_test=0, n=32, levels=5, seed=seed)
    auto = fit(small.X, small.y, 1e-3, 'auto')
    tree = fit(small.X, small.y, 1e-3, 'tree')
    check(auto.oracle_._engine == 'auto', 'method=auto is not on counts_auto')
    check_fit(auto, "method='auto' fit")
    np.testing.assert_allclose(auto.w_, tree.w_, rtol=1e-5, atol=1e-6)
    print("  method='auto' (pairwise kernel) matches method='tree'")


def phase_serve(seed: int, data, svm):
    from repro.serve import RankingService
    w = np.asarray(svm.w_, np.float64)
    rng = np.random.default_rng(seed)
    requests = []
    for n in SERVE_SIZES:
        lo = int(rng.integers(0, data.X_test.shape[0] - n))
        requests.append(data.X_test.row_slice(lo, lo + n).to_dense())
    with RankingService(svm, max_batch=4) as svc:
        n_prog = svc.warmup(max(SERVE_SIZES), ks=(SERVE_K,))
        futures = [svc.submit(np.asarray(X, np.float32), SERVE_K)
                   for X in requests]
        answers = [f.result(timeout=120) for f in futures]
        stats = svc.stats()
    print(f'  warmup compiled {n_prog} programs; served {stats}')
    check(stats['n_programs'] == n_prog, 'serving recompiled after warmup')
    exact = 0
    for X, res in zip(requests, answers):
        s_ref = X @ w
        # Device matmuls may round the f32 operands to bf16 (8 mantissa
        # bits): allow 2^-7 of each row's |x| . |w| on every score.
        tol = 2.0 ** -7 * (np.abs(X) @ np.abs(w)) + 1e-7
        check(np.all(np.abs(res.scores - s_ref) <= tol),
              'served scores differ from X @ w')
        ref = np.argsort(-s_ref, kind='stable')[:SERVE_K]
        idx = np.asarray(res.indices)
        check(np.unique(idx).size == SERVE_K, 'duplicate top-k indices')
        check(np.array_equal(res.values, res.scores[idx]),
              'top-k values are not the scores of their indices')
        # Where the order differs from NumPy's, it may only swap scores
        # that are equal within the rounding bound.
        check(np.all(np.abs(s_ref[idx] - s_ref[ref]) <= 2 * tol.max()),
              'top-k order differs from the stable NumPy argsort')
        exact += int(np.array_equal(idx, ref))
    print(f'  {len(requests)} top-{SERVE_K} answers checked; '
          f'{exact}/{len(requests)} identical to the NumPy argsort')


# --------------------------------------------------------------- four chips


def four_chips(seed: int, clock: CompileClock):
    """The sharded fit on a (4, 1) mesh against the one-device tree fit.

    Both fits run SYNC iterations (one compiled chunk each). The same-w
    comparison reads the tree's loss and subgradient at its last iterate
    from its own bundle state (plane slot t is the tangent at iterate t),
    and the sharded oracle's at that w from one more chunk of its already
    compiled fit, started there: no further program is compiled."""
    from repro.core.bmrm import bmrm
    from repro.launch.mesh import make_mesh
    devices = jax.devices()
    check(len(devices) == 4, f'--four-chips needs 4 devices, found '
          f'{len(devices)}')
    mesh = make_mesh((4, 1), ('data', 'model'))
    cfg, data = run_phase('data', clock, paper_data, seed)
    tree = run_phase('tree fit (1 device)', clock, fit, data.X, data.y,
                     cfg.lam, 'tree', SYNC)
    sharded = run_phase('sharded fit (4 devices)', clock, fit, data.X,
                        data.y, cfg.lam, 'sharded', SYNC, mesh=mesh)
    check_fit(tree, 'tree fit', SYNC)
    check_fit(sharded, 'sharded fit', SYNC)
    oracle = sharded.oracle_
    check(oracle.name == 'sharded/csr', f'layout {oracle.name!r}')
    for arr in oracle._args:
        held = {s.device for s in arr.addressable_shards}
        rows = {s.data.shape[0] for s in arr.addressable_shards}
        check(held == set(devices) and rows == {arr.shape[0] // 4},
              f'shards of {arr.shape} are not spread over all 4 devices')
    print('  CSR slot arrays: one row quarter on each of the 4 devices')

    # The same w: the tree fit's last iterate (its best may still be w0).
    state = tree.incremental_.state
    last = tree.report_.iterations - 1
    w = np.asarray(state.S[last])
    lt = tree.report_.loss_history[last]
    at = np.asarray(state.A[last], np.float64)
    res = run_phase('sharded oracle at the tree w', clock, bmrm, oracle,
                    lam=cfg.lam, max_iter=SYNC, solver='device',
                    sync_every=SYNC, w0=w)
    check(np.array_equal(np.asarray(res.state.S[0]), w), 'w0 not kept')
    ls = res.stats.loss_history[0]
    as_ = np.asarray(res.state.A[0], np.float64)
    cos = at @ as_ / (np.linalg.norm(at) * np.linalg.norm(as_))
    print(f'  at the tree fit\'s last iterate (|w| = {np.linalg.norm(w)!r}): '
          f'loss tree {lt!r} sharded {ls!r}, subgradient cosine {cos!r}')
    check(abs(ls - lt) <= 2e-2 * abs(lt) + 2e-2,
          'sharded loss outside bf16 tolerance')
    check(cos > 0.99, 'sharded subgradient outside bf16 tolerance')
    jt, js = tree.report_.objective, sharded.report_.objective
    print(f'  objective after {SYNC} iterations: tree {jt!r} sharded {js!r}')
    check(abs(js - jt) <= 2e-2 * abs(jt), 'objectives differ beyond bf16 '
          'tolerance')


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--four-chips', action='store_true',
                    help='run only the sharded path on a (4, 1) mesh')
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    count = len(jax.devices())
    print(f'[device] platform={dev.platform} kind={dev.device_kind} '
          f'count={count}', flush=True)
    if dev.platform != 'tpu':
        print(f'chip_smoke: needs a TPU, found platform {dev.platform!r}',
              file=sys.stderr)
        return 1
    cache = setup_compile_cache()
    print(f'[cache] persistent compile cache: {cache}', flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()

    if args.four_chips:
        four_chips(args.seed, clock)
    else:
        _, data, svm = run_phase('train', clock, phase_train, args.seed)
        run_phase('reference', clock, phase_reference, args.seed, svm.w_)
        run_phase('kernels', clock, phase_kernels, args.seed)
        run_phase('serve', clock, phase_serve, args.seed, data, svm)
    print(f'[total] {time.perf_counter() - t0:.1f} s wall, '
          f'{clock.seconds:.1f} s compile', flush=True)
    print(json.dumps({'ok': True, 'device': {
        'platform': dev.platform, 'kind': dev.device_kind,
        'count': count}}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
