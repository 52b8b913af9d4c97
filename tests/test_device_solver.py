"""Tests for the device-resident BMRM driver (core.bmrm solver='device'):
the on-device masked bundle QP, host-vs-device driver parity across the
fused oracles, fixed-capacity plane replacement, and the warm-started
regularization path (`RankSVM.path`)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import oracle as O
from repro.core.bmrm import (DEFAULT_MAX_PLANES, bmrm, init_bundle_state)
from repro.core.qp import (project_simplex, project_simplex_masked,
                           solve_bundle_dual, solve_bundle_dual_jax)
from repro.core.ranksvm import RankSVM
from repro.data import cadata_like, grouped_queries


# ------------------------------------------------------------ on-device QP


def test_masked_projection_matches_host_on_full_mask():
    rng = np.random.default_rng(0)
    for k in (1, 4, 17):
        v = rng.uniform(-3, 3, size=k)
        ref = project_simplex(v)
        got = project_simplex_masked(jnp.asarray(v, jnp.float32),
                                     jnp.ones(k, bool))
        np.testing.assert_allclose(np.asarray(got), ref, atol=1e-5)


def test_masked_projection_zeroes_inactive_slots():
    rng = np.random.default_rng(1)
    v = rng.uniform(-2, 2, size=12)
    mask = np.arange(12) < 5
    got = np.asarray(project_simplex_masked(jnp.asarray(v, jnp.float32),
                                            jnp.asarray(mask)))
    np.testing.assert_allclose(got[5:], 0.0)
    np.testing.assert_allclose(got[:5], project_simplex(v[:5]), atol=1e-5)
    assert got.sum() == pytest.approx(1.0, abs=1e-5)


def test_bundle_dual_jax_matches_host_solver():
    rng = np.random.default_rng(2)
    for t, lam in ((1, 0.5), (3, 0.5), (8, 0.02)):
        A = rng.normal(size=(t, 6))
        G = A @ A.T
        b = rng.normal(size=t)
        _, val_h = solve_bundle_dual(G, b, lam)
        K = 12                       # embed in a larger masked buffer
        Gp = np.zeros((K, K))
        Gp[:t, :t] = G
        bp = np.zeros(K)
        bp[:t] = b
        alpha, val_d = solve_bundle_dual_jax(
            jnp.asarray(Gp, jnp.float32), jnp.asarray(bp, jnp.float32),
            lam, jnp.arange(K) < t, n_iter=512)
        alpha = np.asarray(alpha)
        assert float(val_d) == pytest.approx(val_h, rel=1e-3, abs=1e-4)
        np.testing.assert_allclose(alpha[t:], 0.0)
        assert alpha.sum() == pytest.approx(1.0, abs=1e-4)
        assert np.all(alpha >= -1e-6)


def test_device_qp_reaches_the_bundle_optimum_along_a_fit():
    """At every iteration of a device fit the stored dual value lies
    within 0.2% of J below the bundle dual's maximum (host float64
    solve): on these bundles the default 128 FISTA steps alone fell up
    to 6% short, so the QP runs on until its Frank-Wolfe gap is small."""
    d = cadata_like(m=400, m_test=10, seed=0)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    lam, state, worst = 1e-3, None, 0.0
    for _ in range(30):
        state = bmrm(oracle, lam=lam, eps=1e-3, solver='device', max_iter=1,
                     sync_every=1, state=state).state
        n = int(state.n_active)
        G = np.asarray(state.G, np.float64)[:n, :n]
        b = np.asarray(state.b, np.float64)[:n]
        alpha = np.asarray(state.alpha, np.float64)[:n]
        _, d_max = solve_bundle_dual(G, b, lam, alpha0=alpha)
        d_alpha = b @ alpha - alpha @ G @ alpha / (4.0 * lam)
        worst = max(worst, (d_max - d_alpha) / abs(float(state.j_best)))
    assert worst < 2e-3


# --------------------------------------------------- host-vs-device parity


def _parity_case(method, groups=None, m=300, lam=1e-2, eps=1e-3):
    d = cadata_like(m=m, m_test=10, seed=5)
    X, y = d.X, d.y
    if groups is not None:
        X, y, groups = grouped_queries(n_queries=20, per_query=15, seed=2)
    oracle = O.make_oracle(X, y, groups=groups, method=method)
    host = bmrm(oracle, lam=lam, eps=eps, solver='host', max_iter=400)
    dev = bmrm(oracle, lam=lam, eps=eps, solver='device', max_iter=400)
    return host, dev


@pytest.mark.parametrize('method', ['tree', 'pairs'])
def test_host_device_parity_ungrouped(method):
    host, dev = _parity_case(method)
    assert host.stats.solver == 'host'
    assert dev.stats.solver == 'device'
    # same convergence verdict and final objective within the f32 tolerance
    assert host.stats.converged == dev.stats.converged
    assert dev.stats.obj_best == pytest.approx(host.stats.obj_best,
                                               rel=1e-3)


@pytest.mark.parametrize('method', ['tree', 'pairs'])
def test_host_device_parity_grouped(method):
    host, dev = _parity_case(method, groups=True)
    assert host.stats.converged == dev.stats.converged
    assert dev.stats.obj_best == pytest.approx(host.stats.obj_best,
                                               rel=1e-3)


def test_device_gap_is_conservative():
    """The device gap uses the dual value, so at the converged point the
    reported gap still upper-bounds the true suboptimality."""
    d = cadata_like(m=200, m_test=10, seed=6)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    res = bmrm(oracle, lam=1e-2, eps=1e-3, solver='device')
    assert res.stats.converged
    assert res.stats.gap < 1e-3
    # J at the returned w_best matches obj_best (sanity of best-iterate rule)
    loss, _ = oracle.loss_and_subgrad(res.w)
    j = float(loss) + 1e-2 * float(res.w @ res.w)
    assert j == pytest.approx(res.stats.obj_best, rel=1e-4, abs=1e-5)


# -------------------------------------------------- driver dispatch rules


def test_bare_callable_rejects_device_and_auto_falls_back():
    def loss(w):
        return abs(w[0] - 3.0), np.asarray([np.sign(w[0] - 3.0)])

    with pytest.raises(ValueError):
        bmrm(loss, dim=1, lam=0.1, solver='device')
    res = bmrm(loss, dim=1, lam=0.1, eps=1e-8, solver='auto', max_iter=200)
    assert res.stats.solver == 'host'
    assert res.stats.converged


def test_auto_uses_device_for_fused_oracles_and_host_below_f32_floor():
    d = cadata_like(m=150, m_test=10, seed=7)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    res = bmrm(oracle, lam=1e-2, eps=1e-2, solver='auto', max_iter=60)
    assert res.stats.solver == 'device'
    res = bmrm(oracle, lam=1e-2, eps=1e-6, solver='auto', max_iter=5)
    assert res.stats.solver == 'host'


def test_unknown_solver_rejected():
    d = cadata_like(m=50, m_test=10, seed=8)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    with pytest.raises(ValueError):
        bmrm(oracle, solver='gpu')
    with pytest.raises(ValueError):
        RankSVM(solver='gpu')


# ------------------------------------------- fixed-capacity plane buffer


def test_device_max_planes_replacement_still_converges():
    d = cadata_like(m=300, m_test=10, seed=9)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    full = bmrm(oracle, lam=1e-2, eps=1e-3, solver='device', max_iter=400)
    capped = bmrm(oracle, lam=1e-2, eps=1e-3, solver='device', max_iter=400,
                  max_planes=8)
    assert capped.stats.converged
    assert int(capped.state.n_active) == 8
    assert capped.stats.obj_best == pytest.approx(full.stats.obj_best,
                                                  rel=1e-3)


def test_init_bundle_state_shapes():
    st = init_bundle_state(dim=7, max_planes=16)
    assert st.A.shape == (16, 7)
    assert st.G.shape == (16, 16)
    assert int(st.n_active) == 0 and not bool(st.done)


def test_sync_every_auto_converges_no_slower_than_static():
    """sync_every='auto' sizes chunks from the observed gap decay; its
    overshoot is bounded by the final chunk length, so total iterations
    to the same eps must not exceed the static default's by more than
    one maximal chunk (ROADMAP sync autotuning). On this problem the
    counts are equal; the slack keeps the test honest about what the
    tuner guarantees (overshoot ≤ chunk−1, not a per-trajectory win)."""
    from repro.core.bmrm import AUTO_SYNC_MAX
    d = cadata_like(m=300, m_test=10, seed=21)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    static = bmrm(oracle, lam=1e-2, eps=1e-3, solver='device', max_iter=400)
    auto = bmrm(oracle, lam=1e-2, eps=1e-3, solver='device', max_iter=400,
                sync_every='auto')
    assert auto.stats.converged and static.stats.converged
    assert (auto.stats.iterations
            <= static.stats.iterations + AUTO_SYNC_MAX - 1)
    assert auto.stats.obj_best == pytest.approx(static.stats.obj_best,
                                                rel=1e-3)


def test_next_sync_every_recovers_from_one_step_chunks():
    """A 1-step chunk yields a single gap sample; the tuner must be able
    to grow back out of cur=1 instead of paying a host round-trip per
    iteration forever (code-review finding)."""
    from repro.core.bmrm import AUTO_SYNC_MAX, _next_sync_every
    assert _next_sync_every(np.asarray([0.5]), eps=1e-3, cur=1) == 2
    assert _next_sync_every(np.asarray([]), eps=1e-3, cur=4) == 8
    # converged-looking gap: keep the (small) current chunk
    assert _next_sync_every(np.asarray([5e-4]), eps=1e-3, cur=1) == 1
    # growth stays capped
    assert _next_sync_every(np.asarray([0.5]), eps=1e-3,
                            cur=AUTO_SYNC_MAX) == AUTO_SYNC_MAX


def test_sync_every_rejects_unknown_string():
    d = cadata_like(m=60, m_test=10, seed=22)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    with pytest.raises(ValueError, match='sync_every'):
        bmrm(oracle, solver='device', sync_every='adaptive')
    with pytest.raises(ValueError, match='sync_every'):
        RankSVM(sync_every='adaptive')


def test_ranksvm_accepts_sync_every_auto():
    d = cadata_like(m=150, m_test=10, seed=23)
    svm = RankSVM(lam=1e-2, eps=1e-2, method='tree', solver='device',
                  sync_every='auto').fit(d.X, d.y)
    assert svm.report_.converged
    assert svm.report_.solver == 'device'


def test_device_iterations_run_in_sync_chunks():
    d = cadata_like(m=200, m_test=10, seed=10)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    res = bmrm(oracle, lam=1e-2, eps=0.0, solver='device', max_iter=10,
               sync_every=4)
    # eps=0 never converges: 10 iterations round up to 3 chunks of 4
    assert res.stats.iterations == 12
    assert len(res.stats.loss_history) == 12
    assert not res.stats.converged


# ------------------------------------------------- regularization path


def test_path_matches_cold_fits_and_reuses_state():
    # mode='sequential' pinned: this test covers the warm-started bundle
    # state threading (the vmap mode has its own suite, test_path_sweep.py)
    d = cadata_like(m=250, m_test=10, seed=11)
    lams = [1e-1, 1e-2, 1e-3]
    svm = RankSVM(eps=1e-3, method='tree', solver='device')
    points = svm.path(d.X, d.y, lams, mode='sequential')
    assert [p.lam for p in points] == lams
    total_warm = 0
    for p in points:
        assert p.report.converged
        cold = RankSVM(lam=p.lam, eps=1e-3, method='tree',
                       solver='device').fit(d.X, d.y)
        assert p.report.objective == pytest.approx(cold.report_.objective,
                                                   rel=2e-3)
        total_warm += p.report.iterations
    # estimator is left fitted at the last lambda
    assert svm.lam == lams[-1]
    np.testing.assert_allclose(svm.w_, points[-1].w)
    # warm-started sweep must not exceed the cold per-lam iteration budget
    cold_last = RankSVM(lam=lams[-1], eps=1e-3, method='tree',
                        solver='device').fit(d.X, d.y)
    assert points[-1].report.iterations <= cold_last.report_.iterations


def test_path_host_solver_warm_starts_w():
    d = cadata_like(m=150, m_test=10, seed=12)
    svm = RankSVM(eps=1e-2, method='tree', solver='host')
    points = svm.path(d.X, d.y, [1e-1, 1e-2])
    assert all(p.report.converged for p in points)
    assert all(p.report.solver == 'host' for p in points)


def test_path_rejects_empty_lams():
    d = cadata_like(m=60, m_test=10, seed=13)
    with pytest.raises(ValueError):
        RankSVM().path(d.X, d.y, [])


def test_warm_state_shape_mismatch_rejected():
    d = cadata_like(m=80, m_test=10, seed=14)
    oracle = O.make_oracle(d.X, d.y, method='tree')
    res = bmrm(oracle, lam=1e-2, eps=1e-2, solver='device', max_planes=16)
    with pytest.raises(ValueError):
        bmrm(oracle, lam=1e-3, solver='device', max_planes=32,
             state=res.state)
    with pytest.raises(ValueError):
        bmrm(oracle, lam=1e-3, solver='host', state=res.state)


def test_default_max_planes_constant_sane():
    assert DEFAULT_MAX_PLANES >= 64
