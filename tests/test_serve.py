"""Serving-layer suite: bucketed scorer parity (incl. ties and every
bucket boundary), zero steady-state recompiles, micro-batcher
correctness under concurrency, and atomic hot-swap version integrity.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.ranksvm import RankSVM
from repro.serve import (MicroBatcher, RankingService, Scorer, WeightStore,
                         bucket_for)

RNG = np.random.default_rng(7)
D = 8


def _problem(n, d=D, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    return X, w


# -- bucketing ---------------------------------------------------------------

def test_bucket_for_boundaries():
    assert bucket_for(1) == 64
    assert bucket_for(63) == 64
    assert bucket_for(64) == 64
    assert bucket_for(65) == 128
    assert bucket_for(128) == 128
    assert bucket_for(129) == 256
    assert bucket_for(3, min_bucket=2) == 4
    with pytest.raises(ValueError, match='n >= 1'):
        bucket_for(0)


@pytest.mark.parametrize('n', [1, 2, 63, 64, 65, 127, 128, 129, 255, 256,
                               257])
def test_scores_parity_across_boundaries(n):
    """Padding must be exactly invisible: scores at every bucket edge
    match the plain matmul."""
    X, w = _problem(n, seed=n)
    sc = Scorer(w)
    np.testing.assert_allclose(sc.scores(X), X @ w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('n,k', [(1, 1), (5, 3), (64, 64), (65, 1),
                                 (65, 64), (129, 100), (200, 7)])
def test_top_k_parity_vs_argsort(n, k):
    X, w = _problem(n, seed=n + 100)
    sc = Scorer(w)
    s = sc.scores(X)
    vals, idx = sc.top_k(X, k)
    ref = np.argsort(-s, kind='stable')[:k]
    np.testing.assert_array_equal(idx, ref)
    np.testing.assert_array_equal(vals, s[ref])


def test_top_k_duplicate_scores_tie_rule():
    """Exact ties (identical rows -> identical device scores) break
    lowest-index-first, bit-consistent with a stable full argsort."""
    X, w = _problem(4, seed=3)
    Xt = np.repeat(X, 5, axis=0)            # every score appears 5x
    sc = Scorer(w)
    s = sc.scores(Xt)
    vals, idx = sc.top_k(Xt, 12)
    ref = np.argsort(-s, kind='stable')[:12]
    np.testing.assert_array_equal(idx, ref)
    np.testing.assert_array_equal(vals, s[ref])
    # all-equal scores: top-k is the identity prefix
    Xc = np.repeat(X[:1], 9, axis=0)
    _, idx = sc.top_k(Xc, 6)
    np.testing.assert_array_equal(idx, np.arange(6))


def test_top_k_k_larger_than_candidates():
    X, w = _problem(10)
    vals, idx = Scorer(w).top_k(X, 99)      # clamped: everything, ranked
    assert idx.shape == (10,)
    np.testing.assert_array_equal(np.sort(idx), np.arange(10))


def test_request_validation_errors():
    _, w = _problem(4)
    sc = Scorer(w)
    with pytest.raises(ValueError, match='empty candidate set'):
        sc.scores(np.zeros((0, D), np.float32))
    with pytest.raises(ValueError, match='2-D'):
        sc.scores(np.zeros(D, np.float32))
    with pytest.raises(ValueError, match='width'):
        sc.scores(np.zeros((3, D + 1), np.float32))
    for bad_k in (0, -1, 2.5, True):
        with pytest.raises(ValueError, match='positive integer'):
            sc.top_k(np.zeros((3, D), np.float32), bad_k)
    with pytest.raises(ValueError, match='min_bucket'):
        Scorer(w, min_bucket=0)


def test_zero_steady_state_recompiles():
    """After warmup over the traffic's size range, serving any mix of
    sizes/ks in range must not grow the compile cache: program count
    stable AND every jitted program's cache size stays 1."""
    _, w = _problem(1)
    sc = Scorer(w)
    rng = np.random.default_rng(5)
    # warmup: one representative of every (bucket, k-bucket) in range
    for n in (64, 128):
        k = 1
        while k <= n:                       # every k-bucket of this bucket
            sc.top_k(rng.normal(size=(n, D)).astype(np.float32), k)
            k *= 2
        sc.scores(rng.normal(size=(n, D)).astype(np.float32))
    warm_programs = sc.n_programs
    warm_sizes = sc.program_cache_sizes()
    assert all(v == 1 for v in warm_sizes.values())
    # steady state: 60 random requests inside the warmed range
    for _ in range(60):
        n = int(rng.integers(1, 129))
        k = int(rng.integers(1, n + 1))
        sc.top_k(rng.normal(size=(n, D)).astype(np.float32), k)
    assert sc.n_programs == warm_programs
    assert sc.program_cache_sizes() == warm_sizes


def test_warm_covers_batched_traffic():
    """After RankingService.warmup over the traffic envelope, ANY mix of
    request sizes / ks / flush sizes inside it compiles nothing new —
    including the micro-batcher's coalesced (batch-bucket, m-bucket)
    programs, whose first-seen-mid-traffic compile was a real latency
    spike before warm() existed."""
    _, w = _problem(1)
    rng = np.random.default_rng(31)
    with RankingService(w, max_batch=8, max_delay_ms=50.0) as svc:
        svc.warmup(200, ks=(5,), grouped=True)
        warm_programs = svc.scorer.n_programs
        warm_sizes = svc.scorer.program_cache_sizes()
        for _ in range(6):                  # bursts -> varied flush sizes
            futs = [svc.submit(
                rng.normal(size=(int(rng.integers(1, 201)),
                                 D)).astype(np.float32), 5)
                for _ in range(int(rng.integers(1, 9)))]
            for f in futs:
                f.result(30.0)
        n = 37
        svc.rank_grouped(rng.normal(size=(n, D)).astype(np.float32),
                         np.zeros(n, np.int32))
        assert svc.scorer.n_programs == warm_programs
        assert svc.scorer.program_cache_sizes() == warm_sizes


def test_rank_grouped_parity_with_lexsort():
    X, w = _problem(50, seed=11)
    Xt = np.concatenate([X, X[:10]])        # exact in-group score ties
    g = np.asarray(RNG.integers(0, 5, size=60), np.int32)
    sc = Scorer(w)
    s = sc.scores(Xt)
    order = sc.rank_grouped(Xt, g)
    # lexsort: last key primary -> (group asc, score desc); stable, so
    # equal (group, score) keep index order
    ref = np.lexsort((-s.astype(np.float64), g))
    np.testing.assert_array_equal(order, ref)
    with pytest.raises(ValueError, match='align'):
        sc.rank_grouped(Xt, g[:-1])


def test_rank_grouped_noncontiguous_singleton_groups():
    X, w = _problem(7, seed=2)
    g = np.array([3, 0, 3, 2, 0, 1, 3], np.int32)
    sc = Scorer(w)
    s = sc.scores(X)
    order = sc.rank_grouped(X, g)
    np.testing.assert_array_equal(order,
                                  np.lexsort((-s.astype(np.float64), g)))


# -- weight store ------------------------------------------------------------

def test_weight_store_versions_and_validation():
    _, w = _problem(1)
    store = WeightStore(w)
    assert store.version == 0 and store.n_features == D
    assert store.swap(w * 2) == 1
    assert store.swap(w * 3) == 2
    v, wd = store.get()
    assert v == 2
    np.testing.assert_allclose(np.asarray(wd), w * 3, rtol=1e-6)
    with pytest.raises(ValueError, match='does not match'):
        store.swap(np.zeros(D + 1, np.float32))
    with pytest.raises(ValueError, match='non-finite'):
        store.swap(np.full(D, np.nan, np.float32))
    with pytest.raises(ValueError, match='1-D'):
        WeightStore(np.zeros((2, 2), np.float32))


def test_weight_store_accepts_estimator_and_pathpoint():
    X, w = _problem(40, seed=9)
    y = X @ w + 0.1 * RNG.normal(size=40)
    est = RankSVM(max_iter=50).fit(X, y)
    store = WeightStore(est)                # takes est.w_
    np.testing.assert_allclose(np.asarray(store.get()[1]), est.w_,
                               rtol=1e-6)
    pts = est.path(X, y, [1e-2, 1e-3], mode='sequential')
    store.swap(pts[0])                      # takes PathPoint.w
    np.testing.assert_allclose(np.asarray(store.get()[1]), pts[0].w,
                               rtol=1e-6)
    with pytest.raises(ValueError, match='None'):
        WeightStore(RankSVM())              # unfitted


# -- micro-batcher -----------------------------------------------------------

def test_microbatcher_parity_and_coalescing():
    """A burst submitted inside one delay window coalesces into few
    launches, and every response matches the direct scorer."""
    _, w = _problem(1)
    sc = Scorer(w)
    reqs = []
    rng = np.random.default_rng(13)
    for i in range(12):
        n = int(rng.integers(1, 90))
        X = rng.normal(size=(n, D)).astype(np.float32)
        k = None if i % 3 == 0 else int(rng.integers(1, n + 1))
        reqs.append((X, k))
    with MicroBatcher(sc, max_batch=16, max_delay_ms=200.0) as mb:
        futures = [mb.submit(X, k) for X, k in reqs]
        responses = [f.result(30.0) for f in futures]
        assert mb.n_batches <= 2            # burst coalesced
        assert mb.n_requests == 12
    for (X, k), r in zip(reqs, responses):
        np.testing.assert_allclose(r.scores, sc.scores(X), rtol=1e-5,
                                   atol=1e-5)
        if k is None:
            assert r.values.size == 0 and r.indices.size == 0
        else:
            vals, idx = sc.top_k(X, k)
            np.testing.assert_array_equal(r.indices, idx)
            np.testing.assert_allclose(r.values, vals, rtol=1e-5,
                                       atol=1e-5)


def test_microbatcher_validation_in_caller_thread():
    _, w = _problem(1)
    with MicroBatcher(Scorer(w), max_delay_ms=1.0) as mb:
        with pytest.raises(ValueError, match='width'):
            mb.submit(np.zeros((3, D + 1), np.float32))
        with pytest.raises(ValueError, match='empty candidate set'):
            mb.submit(np.zeros((0, D), np.float32))
        # the worker is unharmed: a good request still serves
        X, _ = _problem(5)
        np.testing.assert_allclose(mb.scores(X), X @ w, rtol=1e-5,
                                   atol=1e-5)


def test_microbatcher_worker_error_propagates_and_recovers():
    _, w = _problem(1)
    sc = Scorer(w)
    boom = {'armed': True}
    orig = sc.score_batch

    def flaky(requests):
        if boom.pop('armed', False):
            raise RuntimeError('injected device failure')
        return orig(requests)

    sc.score_batch = flaky
    with MicroBatcher(sc, max_delay_ms=1.0) as mb:
        X, _ = _problem(4)
        with pytest.raises(RuntimeError, match='injected'):
            mb.submit(X).result(30.0)
        np.testing.assert_allclose(mb.scores(X), X @ w, rtol=1e-5,
                                   atol=1e-5)


def test_microbatcher_close_flushes_then_rejects():
    _, w = _problem(1)
    mb = MicroBatcher(Scorer(w), max_batch=64, max_delay_ms=500.0)
    X, _ = _problem(6)
    futures = [mb.submit(X) for _ in range(5)]
    mb.close()                              # flushes the queued 5
    for f in futures:
        np.testing.assert_allclose(f.result(1.0).scores, X @ w,
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(RuntimeError, match='closed'):
        mb.submit(X)


def test_microbatcher_bounded_queue_under_flood():
    """A tiny queue bound + many producer threads: backpressure blocks
    submitters instead of growing the queue, and everything completes."""
    _, w = _problem(1)
    with MicroBatcher(Scorer(w), max_batch=2, max_delay_ms=0.0,
                      max_queue=2) as mb:
        X, _ = _problem(3)
        results, errors = [], []

        def produce():
            try:
                for _ in range(10):
                    results.append(mb.submit(X).result(30.0))
            except Exception as e:          # pragma: no cover - fails test
                errors.append(e)

        threads = [threading.Thread(target=produce) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not errors and len(results) == 40
        for r in results:
            np.testing.assert_allclose(r.scores, X @ w, rtol=1e-5,
                                       atol=1e-5)
    with pytest.raises(ValueError, match='max_queue'):
        MicroBatcher(Scorer(w), max_batch=8, max_queue=4)


def test_hot_swap_single_version_per_response():
    """Concurrent traffic + repeated swaps: every response must have been
    produced ENTIRELY by exactly one weight version. Versions are scaled
    far apart (w * 2^v), so a response mixing two versions — or scored
    with a version other than the one it reports — fails its closeness
    check against the reported version's exact scores and matches no
    other version's."""
    _, w0 = _problem(1, seed=21)
    w0 = 0.5 + np.abs(w0)                   # well away from 0
    store = WeightStore(w0)
    # every version precomputed: the dict is never mutated once traffic
    # starts, so clients can iterate it lock-free
    weights = {v: (w0 * float(2 ** v)).astype(np.float32)
               for v in range(13)}
    scorer = Scorer(store)
    # warm every program the traffic below can hit (candidate buckets up to
    # 40 rows, k up to 3, coalesced batches up to 8) so in-flight traffic
    # is fast enough to straddle several swaps: a first compile mid-traffic
    # outlasts all twelve of them
    scorer.warm(40, ks=(1, 2, 3), max_batch=8)
    with MicroBatcher(scorer, max_batch=8, max_delay_ms=1.0) as mb:
        mb.submit(np.zeros((40, D), np.float32), 3).result(30.0)
        stop = threading.Event()
        checked = []
        errors = []

        def swapper():
            for v in range(1, 13):
                if stop.is_set():
                    break
                assert store.swap(weights[v]) == v
                time.sleep(0.005)
            stop.set()

        def client(seed):
            rng = np.random.default_rng(seed)
            try:
                while not stop.is_set():
                    n = int(rng.integers(1, 40))
                    X = rng.normal(size=(n, D)).astype(np.float32)
                    r = mb.submit(X, min(3, n)).result(30.0)
                    expect = X @ weights[r.version]
                    np.testing.assert_allclose(r.scores, expect,
                                               rtol=1e-4, atol=1e-4)
                    # no OTHER version could have produced these scores
                    others = [v for v in weights if v != r.version]
                    for v in others:
                        alt = X @ weights[v]
                        if not np.allclose(alt, expect, rtol=1e-3):
                            assert not np.allclose(r.scores, alt,
                                                   rtol=1e-3)
                    checked.append(r.version)
            except Exception as e:
                errors.append(e)
                stop.set()

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(3)]
        sw = threading.Thread(target=swapper)
        for t in threads + [sw]:
            t.start()
        for t in threads + [sw]:
            t.join(120.0)
        if errors:
            raise errors[0]
        assert len(checked) > 0
        assert len(set(checked)) > 1        # traffic spanned >= 2 versions


# -- service + estimator wrappers --------------------------------------------

def test_ranking_service_modes_and_stats():
    _, w = _problem(1)
    X, _ = _problem(20, seed=4)
    with RankingService(w, max_delay_ms=1.0) as svc:
        np.testing.assert_allclose(svc.scores(X), X @ w, rtol=1e-5,
                                   atol=1e-5)
        vals, idx = svc.top_k(X, 4)
        assert idx.shape == (4,)
        st = svc.stats()
        assert st['n_requests'] == 2 and st['version'] == 0
        assert svc.swap_weights(w * 2) == 1
        np.testing.assert_allclose(svc.scores(X), 2 * (X @ w),
                                   rtol=1e-4, atol=1e-4)
    direct = RankingService(w, micro_batch=False)
    np.testing.assert_allclose(direct.scores(X), X @ w, rtol=1e-5,
                               atol=1e-5)
    with pytest.raises(RuntimeError, match='micro_batch=True'):
        direct.submit(X)
    g = np.zeros(20, np.int32)
    s = direct.scores(X)
    np.testing.assert_array_equal(
        direct.rank_grouped(X, g),
        np.lexsort((-s.astype(np.float64), g)))
    direct.close()                          # no batcher: a no-op


def test_ranksvm_scores_topk_wrappers():
    X, w = _problem(60, seed=17)
    y = X @ w + 0.05 * RNG.normal(size=60)
    est = RankSVM(max_iter=80).fit(X, y)
    s = est.scores(X)
    np.testing.assert_allclose(s, est.decision_function(X), rtol=1e-4,
                               atol=1e-4)
    vals, idx = est.top_k(X, 5)
    np.testing.assert_array_equal(idx, np.argsort(-s, kind='stable')[:5])
    # scorer cache: same object until refit
    assert est.scorer() is est.scorer()
    first = est.scorer()
    est.fit(X, y)
    assert est.scorer() is not first
    un = RankSVM()
    for call in (lambda: un.scores(X), lambda: un.top_k(X, 2),
                 lambda: un.scorer()):
        with pytest.raises(RuntimeError, match='fit'):
            call()


def test_ranksvm_scores_sparse_fallback():
    from repro.data.sparse import CSRMatrix
    X, w = _problem(30, seed=23)
    y = X @ w
    est = RankSVM(max_iter=60).fit(X, y)
    Xs = CSRMatrix.from_dense(X)
    np.testing.assert_allclose(est.scores(Xs), est.decision_function(Xs),
                               rtol=1e-6)


def test_scorer_thread_safety_direct():
    _, w = _problem(1)
    sc = Scorer(w)
    errors = []

    def hammer(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(25):
                n = int(rng.integers(1, 70))
                X = rng.normal(size=(n, D)).astype(np.float32)
                np.testing.assert_allclose(sc.scores(X), X @ w,
                                           rtol=1e-4, atol=1e-4)
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(s,))
               for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
    assert not errors


# -- adaptive coalescing window ----------------------------------------------


def test_fixed_window_is_the_default():
    """Without adaptive_delay the effective window never moves off
    max_delay, however sparse the arrivals."""
    _, w = _problem(1)
    X, _ = _problem(3)
    with MicroBatcher(Scorer(w), max_delay_ms=20.0) as mb:
        assert mb.effective_delay_ms == 20.0
        for _ in range(3):
            mb.scores(X)
            time.sleep(0.05)
        assert mb.effective_delay_ms == 20.0


def test_adaptive_window_collapses_under_sparse_traffic():
    """Arrival gaps past the window mean waiting cannot coalesce
    anything: the EWMA drives the effective window to zero (immediate
    flush, per-request p50 recovered)."""
    _, w = _problem(1)
    X, _ = _problem(3)
    with MicroBatcher(Scorer(w), max_delay_ms=20.0,
                      adaptive_delay=True) as mb:
        assert mb.effective_delay_ms == 20.0    # no samples yet
        for _ in range(4):
            mb.scores(X)
            time.sleep(0.08)                    # gap = 4x the window
        assert mb.effective_delay_ms == 0.0


def test_adaptive_window_stays_open_under_dense_traffic():
    """Back-to-back arrivals (gaps << window) must keep (nearly) the
    whole coalescing window — dense traffic is what the window is FOR."""
    _, w = _problem(1)
    sc = Scorer(w)
    X, _ = _problem(3)
    with MicroBatcher(sc, max_batch=64, max_delay_ms=50.0,
                      adaptive_delay=True) as mb:
        futures = [mb.submit(X) for _ in range(30)]     # one tight burst
        eff = mb.effective_delay_ms
        for f in futures:
            f.result(30.0)
        assert eff > 0.8 * 50.0
        assert mb.mean_batch > 1.0              # the burst still coalesced


def test_adaptive_window_recovers_after_idle_spell():
    """The 4x-window clamp bounds how far one long idle gap can push the
    estimate: a dense burst after an idle spell reopens the window within
    a handful of arrivals instead of tens."""
    _, w = _problem(1)
    X, _ = _problem(2)
    with MicroBatcher(Scorer(w), max_delay_ms=20.0,
                      adaptive_delay=True) as mb:
        mb.scores(X)
        time.sleep(0.5)                         # idle; clamped to 80 ms
        mb.scores(X)
        assert mb.effective_delay_ms == 0.0
        futures = [mb.submit(X) for _ in range(12)]     # dense burst
        eff = mb.effective_delay_ms
        for f in futures:
            f.result(30.0)
        assert eff > 0.5 * 20.0


def test_adaptive_service_serves_correctly():
    """End to end through RankingService: adaptive coalescing changes
    latency, never results."""
    X, w = _problem(40, seed=21)
    with RankingService(w, adaptive_delay=True, max_delay_ms=5.0) as svc:
        np.testing.assert_allclose(svc.scores(X), X @ w, rtol=1e-5,
                                   atol=1e-5)
        vals, idx = svc.top_k(X, 7)
        s = svc.scores(X)
        ref = np.argsort(-s, kind='stable')[:7]
        np.testing.assert_array_equal(idx, ref)
        assert svc.batcher.effective_delay_ms <= 5.0
