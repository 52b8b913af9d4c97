"""Incremental-retraining suite (DESIGN.md §11): plane-ledger algebra
(append-then-retire bit-identity, lower-bound validity of revalidated
planes), `RankSVM.refit` warm-start quality vs cold fits and the w-only
fallback, `BlockStore` append/retire semantics, checkpointed resume
mid-refit through the runtime loop, and the train→refit→hot-swap serving
smoke the CI fast job runs."""

import numpy as np
import jax
import pytest

from repro.core import oracle as O
from repro.core.bmrm import DEFAULT_MAX_PLANES, bmrm, init_bundle_state
from repro.core.incremental import (BaseRetireError, IncrementalFit,
                                    LedgerBlock, PlaneLedger, block_partials,
                                    refit_chunk_step)
from repro.core.ranksvm import REFIT_MODES, RankSVM
from repro.data import BlockStore, CSRMatrix, cadata_drift, cadata_like
from repro.runtime import LoopConfig, SimulatedPreemption, run

EPS = 1e-3


def _drift(m=800, frac=0.1, seed=0):
    base, Xd, yd = cadata_drift(m=m, m_delta=max(8, int(m * frac)),
                                seed=seed)
    return base, Xd, yd


def _fit(X, y, **kw):
    kw.setdefault('method', 'tree')
    kw.setdefault('eps', EPS)
    kw.setdefault('max_iter', 400)
    return RankSVM(**kw).fit(X, y)


# ------------------------------------------------------- ledger algebra


def _toy_ledger(P=5, n=6, seed=0):
    rng = np.random.default_rng(seed)
    S = rng.normal(size=(P, n))
    alpha = rng.dirichlet(np.ones(P))
    base = LedgerBlock(rng.normal(size=P), rng.normal(size=(P, n)), 40)
    return PlaneLedger(S, alpha, base, base_bids=(0, 1))


def test_ledger_append_then_retire_bit_identical():
    """The pinned-down guarantee: retiring an appended block restores the
    EXACT floating-point planes of the never-appended ledger, because
    `planes()` recomputes sums from immutable components — no `+=`
    accumulation drift."""
    rng = np.random.default_rng(3)
    led = _toy_ledger()
    A0, b0 = led.planes()
    for bid, pairs in ((2, 11), (3, 7)):
        led.append_block(bid, LedgerBlock(rng.normal(size=5),
                                          rng.normal(size=(5, 6)), pairs))
    A1, b1 = led.planes()
    assert not np.array_equal(A1, A0)       # the appends did change them
    led.retire_block(3)
    led.retire_block(2)
    A2, b2 = led.planes()
    np.testing.assert_array_equal(A2, A0)
    np.testing.assert_array_equal(b2, b0)


def test_ledger_round_trip_through_real_fit():
    """Same bit-identity through the full stack: fitted state -> ledger
    -> real oracle partials for an appended block -> retire."""
    base, Xd, yd = _drift(m=300)
    svm = _fit(base.X, base.y)
    inc = svm.incremental_
    assert inc is not None and inc.ledger is not None
    A0, b0 = inc.ledger.planes()
    bid = inc.append(Xd, yd)
    inc.retire(bid)
    A1, b1 = inc.ledger.planes()
    np.testing.assert_array_equal(A1, A0)
    np.testing.assert_array_equal(b1, b0)


def test_ledger_validation_errors():
    led = _toy_ledger()
    rng = np.random.default_rng(1)
    ok = LedgerBlock(rng.normal(size=5), rng.normal(size=(5, 6)), 3)
    with pytest.raises(ValueError, match='already in the ledger'):
        led.append_block(0, ok)             # base-covered bid
    led.append_block(7, ok)
    with pytest.raises(ValueError, match='already in the ledger'):
        led.append_block(7, ok)             # entry bid
    with pytest.raises(ValueError, match='do not match'):
        led.append_block(8, LedgerBlock(np.zeros(4), np.zeros((4, 6)), 1))
    with pytest.raises(BaseRetireError, match='base component'):
        led.retire_block(1)
    with pytest.raises(ValueError, match='not in the ledger'):
        led.retire_block(99)
    with pytest.raises(ValueError, match='do not align'):
        PlaneLedger(np.zeros((3, 4)), np.zeros(2),
                    LedgerBlock(np.zeros(3), np.zeros((3, 4)), 1), ())
    with pytest.raises(ValueError, match='base component'):
        PlaneLedger(np.zeros((3, 4)), np.zeros(3),
                    LedgerBlock(np.zeros(2), np.zeros((2, 4)), 1), ())


def test_ledger_planes_need_pairs():
    led = PlaneLedger(np.zeros((2, 3)), np.zeros(2),
                      LedgerBlock(np.zeros(2), np.zeros((2, 3)), 0), ())
    with pytest.raises(ValueError, match='no preference pairs'):
        led.planes()


def test_revalidated_planes_lower_bound_merged_risk():
    """The invariant everything rests on: after appending a block, every
    merged plane satisfies a_i @ w + b_i <= R_merged(w) at arbitrary w.
    Ungrouped data makes every base-block pair a cross-component pair:
    the planes are normalized by the merged pair count, and with w0 each
    carries the cross pairs' tangent at w0, exact there."""
    base, Xd, yd = _drift(m=400)
    svm = _fit(base.X, base.y)
    inc = svm.incremental_
    inc.append(Xd, yd)
    Xm = np.concatenate([np.asarray(base.X), Xd])
    ym = np.concatenate([base.y, yd])
    merged = O.make_oracle(Xm, ym, method='tree')
    rng = np.random.default_rng(5)
    probes = [np.zeros(Xm.shape[1]), svm.w_,
              *(rng.normal(size=Xm.shape[1]) for _ in range(4))]
    for A, b in (inc.planes(), inc.planes(w0=svm.w_)):
        for w in probes:
            risk, _ = merged.loss_and_subgrad(w)
            cuts = A @ w + b
            # slack for the f32 device state the base planes were read from
            assert cuts.max() <= (float(risk)
                                  + 1e-4 * max(1.0, abs(float(risk))))


def test_revalidated_toppush_planes_lower_bound_merged_risk():
    """'toppush' planes stay lower bounds after an ungrouped append: the
    merged normalizer counts every anchored example, and the dropped
    cross-block part (a merged anchor's loss less its block loss) is
    nonnegative, so no tangent of it is added."""
    base, Xd, yd = _drift(m=300, seed=4)
    svm = _fit(base.X, base.y, loss='toppush')
    inc = svm.incremental_
    inc.append(Xd, yd)
    Xm = np.concatenate([np.asarray(base.X), Xd])
    ym = np.concatenate([base.y, yd])
    merged = O.make_oracle(Xm, ym, method='tree', loss='toppush')
    A, b = inc.planes(w0=svm.w_)
    np.testing.assert_array_equal(A, inc.planes()[0])
    rng = np.random.default_rng(6)
    for w in [np.zeros(Xm.shape[1]), svm.w_,
              *(rng.normal(size=Xm.shape[1]) for _ in range(4))]:
        risk, _ = merged.loss_and_subgrad(w)
        assert (A @ w + b).max() <= (float(risk)
                                     + 1e-4 * max(1.0, abs(float(risk))))


def test_block_partials_matches_scaled_oracle():
    """block_partials is N_block * (loss, subgrad) at each iterate."""
    d = cadata_like(m=120, m_test=10, seed=1)
    S = np.random.default_rng(2).normal(size=(3, d.X.shape[1]))
    blk = block_partials(d.X, d.y, None, S)
    orc = O.make_oracle(d.X, d.y, method='tree')
    assert blk.n_pairs == orc.n_pairs
    for i in range(3):
        loss, a = orc.loss_and_subgrad(S[i])
        assert blk.ell[i] == pytest.approx(blk.n_pairs * float(loss),
                                           rel=1e-6)
        np.testing.assert_allclose(blk.g[i],
                                   blk.n_pairs * np.asarray(a, np.float64),
                                   rtol=1e-6, atol=1e-8)


def test_block_partials_pairless_block_is_zero():
    X = np.random.default_rng(0).normal(size=(5, 4))
    y = np.ones(5)                           # constant y: zero pairs
    blk = block_partials(X, y, None, np.zeros((2, 4)))
    assert blk.n_pairs == 0
    np.testing.assert_array_equal(blk.ell, np.zeros(2))
    np.testing.assert_array_equal(blk.g, np.zeros((2, 4)))


# ----------------------------------------------------------- BlockStore


def test_blockstore_cross_boundary_ops_match_numpy():
    rng = np.random.default_rng(4)
    parts = [rng.normal(size=(m, 5)) for m in (7, 11, 3)]
    store = BlockStore()
    for P in parts:
        store.append(P, rng.normal(size=P.shape[0]))
    dense = np.concatenate(parts)
    assert (store.m, store.n) == dense.shape
    w = rng.normal(size=5)
    v = rng.normal(size=store.m)
    np.testing.assert_allclose(store.block(4, 16), dense[4:16])
    np.testing.assert_allclose(store.matvec_block(0, store.m, w), dense @ w,
                               rtol=1e-12)
    np.testing.assert_allclose(store.rmatvec_block(2, 20, v[2:20]),
                               dense[2:20].T @ v[2:20], rtol=1e-12)


def test_blockstore_retire_and_member_range():
    rng = np.random.default_rng(6)
    store = BlockStore()
    for m in (4, 6, 5):
        store.append(rng.normal(size=(m, 3)), rng.normal(size=m))
    assert store.block_ids == (0, 1, 2)
    assert store.member_range(1) == (4, 10)
    y1 = store.member(1).y
    store.retire(0)
    assert store.block_ids == (1, 2)
    assert store.m == 11
    assert store.member_range(1) == (0, 6)
    np.testing.assert_array_equal(store.y[:6], y1)
    with pytest.raises(ValueError, match='retained'):
        store.retire(0)


def test_blockstore_validation():
    store = BlockStore()
    store.append(np.zeros((3, 4)), np.arange(3.0))
    with pytest.raises(ValueError, match='features'):
        store.append(np.zeros((2, 5)), np.zeros(2))     # n mismatch
    with pytest.raises(ValueError, match='y'):
        store.append(np.zeros((2, 4)), np.zeros(3))     # y length
    with pytest.raises(ValueError, match='group'):
        store.append(np.zeros((2, 4)), np.zeros(2), groups=np.zeros(2,
                                                                    int))
    with pytest.raises(ValueError, match='BlockStore'):
        store.append(BlockStore(), np.zeros(0))         # no nesting


def test_blockstore_grouped_all_or_none():
    store = BlockStore()
    store.append(np.zeros((2, 3)), np.arange(2.0), groups=np.zeros(2, int))
    with pytest.raises(ValueError, match='group'):
        store.append(np.zeros((2, 3)), np.arange(2.0))  # missing groups
    store.append(np.zeros((2, 3)), np.arange(2.0), groups=np.ones(2, int))
    g = store.groups
    np.testing.assert_array_equal(g, [0, 0, 1, 1])


def test_blockstore_csr_materialize_merges():
    rng = np.random.default_rng(8)
    dense = (rng.random(size=(12, 6)) < 0.3) * rng.normal(size=(12, 6))
    a, b = CSRMatrix.from_dense(dense[:5]), CSRMatrix.from_dense(dense[5:])
    store = BlockStore()
    store.append(a, rng.normal(size=5))
    store.append(b, rng.normal(size=7))
    merged = store.materialize()
    assert isinstance(merged, CSRMatrix)
    np.testing.assert_array_equal(merged.to_dense(), dense)
    assert not store.disk_backed
    with pytest.raises(ValueError, match='empty'):
        BlockStore().materialize()


# -------------------------------------------------------- refit quality


def test_refit_ledger_beats_cold_and_matches_objective():
    """The PR's acceptance bar: after appending a 10% block, the ledger
    refit reaches the same eps in <= 0.5x the cold fit's iterations, at
    an objective inside the eps envelope."""
    base, Xd, yd = _drift(m=800, frac=0.1)
    svm = _fit(base.X, base.y)
    rep = svm.refit(Xd, yd, mode='ledger')
    assert rep.mode == 'ledger'
    assert rep.n_planes > 0
    assert rep.delta_rows == len(yd)
    assert rep.fit.converged

    Xm = np.concatenate([np.asarray(base.X), Xd])
    ym = np.concatenate([base.y, yd])
    cold = _fit(Xm, ym)
    assert cold.report_.converged
    assert rep.fit.iterations <= 0.5 * cold.report_.iterations
    assert abs(rep.fit.objective - cold.report_.objective) <= 2 * EPS


def test_refit_ledger_no_worse_than_w_only():
    base, Xd, yd = _drift(m=400, frac=0.1, seed=1)
    led = _fit(base.X, base.y)
    won = _fit(base.X, base.y)
    r_led = led.refit(Xd, yd, mode='ledger')
    r_won = won.refit(Xd, yd, mode='w-only')
    assert r_led.fit.converged and r_won.fit.converged
    assert r_won.mode == 'w-only' and r_won.n_planes == 0
    # never worse = within the shared eps envelope of the same optimum,
    # and never more iterations than the plane-free warm start needs
    assert r_led.fit.objective <= r_won.fit.objective + EPS
    assert r_led.fit.iterations <= r_won.fit.iterations


def test_refit_retire_appended_block_is_subtraction():
    """Appending then retiring the same block refits back onto the base
    data with the original planes intact (the exact-subtraction path)."""
    base, Xd, yd = _drift(m=300)
    svm = _fit(base.X, base.y)
    obj0 = svm.report_.objective
    rep1 = svm.refit(Xd, yd, mode='ledger')
    (bid,) = rep1.appended
    rep2 = svm.refit(retire=[bid], mode='ledger')
    assert rep2.mode == 'ledger'
    assert rep2.retired == (bid,) and rep2.appended == ()
    assert svm.incremental_.store.m == len(base.y)
    assert abs(rep2.fit.objective - obj0) <= 2 * EPS


def test_refit_auto_falls_to_w_only_on_base_retire():
    base, Xd, yd = _drift(m=300, seed=2)
    store = BlockStore()
    half = len(base.y) // 2
    store.append(np.asarray(base.X)[:half], base.y[:half])
    store.append(np.asarray(base.X)[half:], base.y[half:])
    svm = _fit(store, None)
    assert svm.incremental_.ledger.base_bids == frozenset({0, 1})
    rep = svm.refit(Xd, yd, retire=[0], mode='auto')
    assert rep.mode == 'w-only'              # base planes not subtractable
    assert rep.n_planes == 0
    assert rep.fit.converged


def test_refit_explicit_ledger_rebuilds_on_base_retire():
    """mode='ledger' + base retire takes the documented expensive path:
    per-block partials over the survivors, planes kept."""
    base, Xd, yd = _drift(m=300, seed=3)
    store = BlockStore()
    half = len(base.y) // 2
    store.append(np.asarray(base.X)[:half], base.y[:half])
    store.append(np.asarray(base.X)[half:], base.y[half:])
    svm = _fit(store, None)
    rep = svm.refit(Xd, yd, retire=[0], mode='ledger')
    assert rep.mode == 'ledger'
    assert rep.n_planes > 0
    assert rep.fit.converged
    assert rep.revalidate_seconds > 0        # the rebuild was paid for


def test_refit_error_paths():
    base, Xd, yd = _drift(m=200)
    with pytest.raises(RuntimeError, match='fit'):
        RankSVM().refit(Xd, yd)
    svm = _fit(base.X, base.y)
    with pytest.raises(ValueError, match='refit mode'):
        svm.refit(Xd, yd, mode='planes')
    with pytest.raises(ValueError, match='append.*retire'):
        svm.refit()
    with pytest.raises(ValueError, match='both X and y'):
        svm.refit(Xd)
    svm.refit(Xd, yd)
    with pytest.raises(ValueError, match='retired every block'):
        svm.refit(retire=list(svm.incremental_.store.block_ids))


def test_refit_ledger_requires_bundle_state():
    base, Xd, yd = _drift(m=200, seed=4)
    host = RankSVM(method='tree', solver='host', eps=EPS,
                   max_iter=400).fit(base.X, base.y)
    assert host.incremental_.ledger is None  # host driver keeps no state
    with pytest.raises(ValueError, match='w-only'):
        host.refit(Xd, yd, mode='ledger')
    rep = host.refit(Xd, yd, mode='auto')    # auto degrades gracefully
    assert rep.mode == 'w-only'
    assert rep.fit.converged


def test_refit_modes_constant():
    assert REFIT_MODES == ('ledger', 'w-only', 'auto')


# ------------------------------------------- checkpointed resume mid-refit


def test_refit_chunk_step_checkpoint_resume_bit_identical(tmp_path):
    """A long refit driven through the fault-tolerant runtime loop:
    preempt mid-run, resume from the checkpoint, and land on EXACTLY the
    bundle state of the uninterrupted run — planes, dual, iterates."""
    d = cadata_like(m=250, m_test=10, seed=9)
    orc = O.make_oracle(d.X, d.y, method='tree')
    step = refit_chunk_step(orc, lam=1e-3, eps=1e-4, sync_every=4)
    init_fn = lambda: init_bundle_state(int(orc.n), DEFAULT_MAX_PLANES)
    batch_fn = lambda s: None

    lc_a = LoopConfig(total_steps=8, ckpt_dir=str(tmp_path / 'a'),
                      ckpt_every=2, async_ckpt=False)
    state_a, rep_a = run(step, init_fn, batch_fn, lc_a)

    lc_b = LoopConfig(total_steps=8, ckpt_dir=str(tmp_path / 'b'),
                      ckpt_every=2, async_ckpt=False)
    with pytest.raises(SimulatedPreemption):
        run(step, init_fn, batch_fn, lc_b, fail_at=5)
    state_b, rep_b = run(step, init_fn, batch_fn, lc_b)
    assert rep_b.resumed_from == 4
    for xa, xb in zip(jax.tree.leaves(state_a), jax.tree.leaves(state_b)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
    # and the loop actually optimized: running objective reached the
    # direct driver's ballpark
    ref = bmrm(orc, lam=1e-3, eps=1e-4, solver='device', max_iter=200)
    assert float(state_a.j_best) <= ref.stats.obj_best * 1.5


# --------------------------------------------- train -> refit -> serve


def test_refit_hot_swaps_into_ranking_service():
    """CI fast-job smoke: fit, append a drifted block, refit under a
    memory budget, hot-swap into a live RankingService, serve."""
    from repro.serve import RankingService
    base, Xd, yd = _drift(m=300, seed=7)
    svm = RankSVM(method='auto', eps=EPS, max_iter=400,
                  memory_budget=1.0).fit(base.X, base.y)
    with RankingService(svm, micro_batch=False) as svc:
        v0 = svc.version
        Xq = np.asarray(base.X_test[:64], np.float32)
        s_old = svc.scores(Xq)
        rep = svm.refit(Xd, yd, weight_store=svc)
        assert rep.fit.converged
        assert svc.version == v0 + 1
        s_new = svc.scores(Xq)
        vals, idx = svc.top_k(Xq, 5)
        ref = np.argsort(-s_new, kind='stable')[:5]
        np.testing.assert_array_equal(idx, ref)
        np.testing.assert_array_equal(vals, s_new[ref])
        assert not np.allclose(s_old, s_new)    # the swap took effect
