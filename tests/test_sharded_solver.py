"""The sharded path as a first-class device-bundle citizen (PR 3).

Covers: `ShardedOracle(groups=...)` parity with `GroupedOracle` (bf16
tolerance) on the degenerate 1-device mesh, host-vs-device-driver parity
for the sharded path, the BundleState sharding annotations, the sparse
(row-sharded CSR slot) input path, and the full-bundle_step dry-run cell.
The streamed per-host assembly half lives in test_sharded_stream.py.

The multi-device half of the file needs a real >1-device mesh; those tests
skip on a bare CPU run and are exercised by the `test-multidevice` CI job
under XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import warnings

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.core import oracle as O
from repro.core.bmrm import (bmrm, abstract_bundle_state,
                             bundle_state_shardings)
from repro.core.distributed import RankSVMShapeConfig
from repro.core.ranksvm import RankSVM
from repro.data import cadata_like, grouped_queries
from repro.data.sparse import random_tfidf
from repro.launch.mesh import make_mesh

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason='needs >= 8 devices (CI: XLA_FLAGS=' '--xla_force_host_platform_device_count=8)')


def _mesh2x4():
    return make_mesh((2, 4), ('data', 'model'))


def _grouped_case(seed=3):
    X, y, groups = grouped_queries(n_queries=24, per_query=16, seed=seed)
    w = np.random.default_rng(seed).normal(size=X.shape[1])
    return X, y, groups, w


def _assert_bf16_close(o_ref, o_sharded, w):
    """Loss within bf16 tolerance, subgradient direction preserved."""
    loss_r, a_r = o_ref.loss_and_subgrad(w)
    loss_s, a_s = o_sharded.loss_and_subgrad(w)
    assert float(loss_s) == pytest.approx(float(loss_r), rel=2e-2, abs=2e-2)
    a_r = np.asarray(a_r, np.float64)
    a_s = np.asarray(a_s, np.float64)
    cos = a_r @ a_s / (np.linalg.norm(a_r) * np.linalg.norm(a_s) + 1e-12)
    assert cos > 0.99


# -------------------------------------------- degenerate 1-device parity


@pytest.mark.parametrize('variant', ['base', 'opt'])
def test_sharded_groups_match_grouped_oracle(variant):
    X, y, groups, w = _grouped_case()
    _assert_bf16_close(
        O.GroupedOracle(X, y, groups),
        O.ShardedOracle(X, y, groups=groups, variant=variant), w)


def test_sharded_groups_n_pairs_and_metadata():
    X, y, groups, _ = _grouped_case()
    so = O.ShardedOracle(X, y, groups=groups)
    go = O.GroupedOracle(X, y, groups)
    assert so.n_pairs == go.n_pairs
    assert so.supports_device_solver and so.prefer_device_solver
    assert so.device_resident


def test_make_oracle_routes_sharded_groups():
    X, y, groups, _ = _grouped_case()
    oracle = O.make_oracle(X, y, groups=groups, method='sharded')
    assert isinstance(oracle, O.ShardedOracle)
    assert oracle.n_pairs == O._exact_pairs(np.asarray(y, np.float32),
                                            groups)


def test_sharded_sparse_group_ids_relabelled_exactly():
    """Hashed/sparse ids must give the same oracle values as compact ids:
    only the NUMBER of groups may set the f32 key-offset magnitude."""
    X, y, groups, w = _grouped_case(seed=12)
    sparse_ids = (np.asarray(groups, np.int64) * 7919 + 10**7).astype(
        np.int32)
    a = O.ShardedOracle(X, y, groups=groups)
    b = O.ShardedOracle(X, y, groups=sparse_ids)
    la, aa = a.loss_and_subgrad(w)
    lb, ab = b.loss_and_subgrad(w)
    assert float(la) == float(lb)
    np.testing.assert_array_equal(np.asarray(aa), np.asarray(ab))


def test_grouped_oracle_sparse_ids_relabelled_exactly():
    """The same id-value invariance must hold on the single-host fused
    training path (GroupedOracle), not just the sharded/metric ones."""
    X, y, groups, w = _grouped_case(seed=14)
    hashed = (np.asarray(groups, np.int64) * 104729 + 10**7).astype(
        np.int32)
    a = O.GroupedOracle(X, y, groups)
    b = O.GroupedOracle(X, y, hashed)
    la, aa = a.loss_and_subgrad(w)
    lb, ab = b.loss_and_subgrad(w)
    assert float(la) == float(lb)
    np.testing.assert_array_equal(np.asarray(aa), np.asarray(ab))


def test_grouped_oracle_many_groups_precision_warns():
    rng = np.random.default_rng(15)
    m, n_groups = 2048, 1024
    X = rng.normal(size=(m, 4))
    y = rng.uniform(0, 1e4, size=m)
    g = np.repeat(np.arange(n_groups), m // n_groups).astype(np.int32)
    with pytest.warns(RuntimeWarning, match='key-offset'):
        O.GroupedOracle(X, y, g)


def test_sharded_many_groups_precision_warns():
    """Past the f32 key-offset envelope the grouped counts go quietly
    wrong (code-review finding); the oracle must say so."""
    rng = np.random.default_rng(13)
    m, n_groups = 2048, 1024
    X = rng.normal(size=(m, 4))
    y = rng.uniform(0, 1e5, size=m)          # huge y range -> huge keys
    g = np.repeat(np.arange(n_groups), m // n_groups).astype(np.int32)
    with pytest.warns(RuntimeWarning, match='key-offset'):
        O.ShardedOracle(X, y, groups=g)


def test_sharded_rejects_explicit_mesh_axes():
    """`jax.make_mesh` gives Explicit axes by default; the mesh bodies'
    sharding constraints need Auto ones, so the oracle refuses such a
    mesh at construction and names the helper that builds the right one."""
    mesh = jax.make_mesh((jax.device_count(), 1), ('data', 'model'))
    X, y, groups, _ = _grouped_case()
    with pytest.raises(ValueError, match='launch.mesh.make_mesh'):
        O.ShardedOracle(X, y, groups=groups, mesh=mesh)


def test_empty_grouped_input_keeps_clean_no_pairs_error():
    """m=0 with groups must still raise the actionable no-pairs error,
    not a numpy reduction crash in the key-scale warning."""
    X = np.zeros((0, 3))
    y = np.zeros(0, np.float32)
    g = np.zeros(0, np.int32)
    with pytest.raises(ValueError, match='preference pairs'):
        O.ShardedOracle(X, y, groups=g)
    with pytest.raises(ValueError, match='preference pairs'):
        O.GroupedOracle(X, y, g)


def test_sharded_groups_validated():
    X, y, groups, _ = _grouped_case()
    bad = np.asarray(groups, np.float64)
    bad[0] = np.nan
    with pytest.raises(ValueError, match='NaN'):
        O.ShardedOracle(X, y, groups=bad)


# ------------------------------------------------- driver parity (1 dev)


def test_sharded_host_vs_device_driver_parity():
    X, y, groups, _ = _grouped_case()
    oracle = O.ShardedOracle(X, y, groups=groups)
    host = bmrm(oracle, lam=1e-2, eps=1e-2, solver='host', max_iter=200)
    dev = bmrm(oracle, lam=1e-2, eps=1e-2, solver='device', max_iter=200)
    assert host.stats.solver == 'host' and dev.stats.solver == 'device'
    assert host.stats.converged and dev.stats.converged
    # both drivers stop at gap < eps, and each obj_best is within its gap
    # of J*, so the principled bound on the difference is eps (= 1e-2)
    assert dev.stats.obj_best == pytest.approx(host.stats.obj_best,
                                               abs=1e-2)


def test_sharded_auto_picks_device_driver():
    X, y, groups, _ = _grouped_case()
    oracle = O.ShardedOracle(X, y, groups=groups)
    res = bmrm(oracle, lam=1e-2, eps=1e-2, solver='auto', max_iter=200)
    assert res.stats.solver == 'device'
    assert res.state is not None


def test_ranksvm_sharded_grouped_device_matches_grouped_host():
    X, y, groups, _ = _grouped_case(seed=4)
    sh = RankSVM(lam=1e-2, eps=1e-2, method='sharded').fit(X, y,
                                                           groups=groups)
    gr = RankSVM(lam=1e-2, eps=1e-2, method='tree').fit(X, y, groups=groups)
    assert sh.report_.solver == 'device'
    assert sh.report_.objective == pytest.approx(gr.report_.objective,
                                                 rel=2e-2)


def test_ranksvm_sharded_path_reuses_state():
    # mode='sequential' pinned: this covers warm-started state threading;
    # the batched (vmap) sharded sweep is tested below
    X, y, groups, _ = _grouped_case(seed=5)
    svm = RankSVM(eps=1e-2, method='sharded')
    points = svm.path(X, y, [1e-1, 1e-2], groups=groups, mode='sequential')
    assert all(p.report.converged for p in points)
    assert all(p.report.solver == 'device' for p in points)
    # warm start: the second lambda must not need more iterations than a
    # cold fit at that lambda
    cold = RankSVM(lam=1e-2, eps=1e-2, method='sharded').fit(
        X, y, groups=groups)
    assert points[-1].report.iterations <= cold.report_.iterations


def test_sharded_path_vmap_matches_sequential():
    """The batched path sweep composes with the mesh oracle: vmap inserts
    a leading (replicated) lambda axis into the oracle body's sharding
    constraints, and `bundle_state_shardings(batched=True)` pins the
    (K, ...)-leading state. Degenerate 1-device mesh here; the >1-device
    case is the multidevice half below."""
    X, y, groups, _ = _grouped_case(seed=6)
    svm = RankSVM(eps=1e-2, method='sharded')
    pv = svm.path(X, y, [1e-1, 1e-2], groups=groups, mode='vmap')
    ps = svm.path(X, y, [1e-1, 1e-2], groups=groups, mode='sequential')
    assert all(p.report.converged for p in pv)
    assert all(p.report.solver == 'vmap' for p in pv)
    for a, b in zip(pv, ps):
        assert a.report.objective == pytest.approx(b.report.objective,
                                                   rel=2e-2, abs=2e-3)


# --------------------------------------------------- sharding annotations


def test_bundle_state_shardings_layout():
    mesh = make_mesh((jax.device_count(), 1), ('data', 'model'))
    sh = bundle_state_shardings(mesh)
    assert sh.A.spec == P(None, 'model')
    for name in ('w', 'w_best', 'b', 'G', 'alpha', 'gap', 'done'):
        assert getattr(sh, name).spec == P()


def test_bundle_state_shardings_batched_layout():
    mesh = make_mesh((jax.device_count(), 1), ('data', 'model'))
    sh = bundle_state_shardings(mesh, batched=True)
    assert sh.A.spec == P(None, None, 'model')
    for name in ('w', 'w_best', 'b', 'G', 'alpha', 'gap', 'done'):
        assert getattr(sh, name).spec == P()


def test_abstract_bundle_state_shapes():
    st = abstract_bundle_state(dim=32, max_planes=16)
    assert st.A.shape == (16, 32) and st.G.shape == (16, 16)
    assert st.w.shape == (32,) and st.done.shape == ()


def test_sharded_csr_trains_without_densification():
    """Acceptance (PR 7): CSR input stays SPARSE on the mesh — no
    projected-GiB densification warning (the PR 3 fallback is gone), the
    slot-layout segment-sum oracle matches the dense tree oracle within
    bf16 tolerance, and `bmrm` trains on it."""
    X = random_tfidf(m=64, n=32, nnz_per_row=4, seed=0)
    y = np.random.default_rng(1).normal(size=64)
    with warnings.catch_warnings():
        warnings.simplefilter('error')       # ANY warning fails the test
        oracle = O.ShardedOracle(X, y)
    assert oracle.name == 'sharded/csr'
    w = np.random.default_rng(2).normal(size=32)
    _assert_bf16_close(O.TreeOracle(np.asarray(X.to_dense()), y),
                       oracle, w)
    res = bmrm(oracle, lam=1e-2, eps=1e-2, solver='device', max_iter=200)
    assert res.stats.converged


def test_sharded_csr_loss_matches_dense_sharded_tightly():
    """Both layouts round (X, w) to the SAME bf16 values before the f32
    matvec, so the only divergence left is XLA's reduction order (exact
    bf16 products reassociated differently) and the count flips of
    near-tie pairs that rounding causes — a much tighter bound than the
    generic f32-vs-bf16 oracle tolerance (2e-2)."""
    X = random_tfidf(m=96, n=24, nnz_per_row=5, seed=3)
    y = np.random.default_rng(4).normal(size=96)
    w = np.random.default_rng(5).normal(size=24)
    dense = O.ShardedOracle(np.asarray(X.to_dense()), y)
    sparse = O.ShardedOracle(X, y)
    ld, _ = dense.loss_and_subgrad(w)
    ls, _ = sparse.loss_and_subgrad(w)
    assert float(ls) == pytest.approx(float(ld), rel=5e-3, abs=5e-3)


def test_sharded_csr_grouped_and_scipy_inputs():
    """Group ids compose with the CSR layout, and a scipy.sparse matrix
    (if available) routes to the same slot path."""
    X = random_tfidf(m=80, n=16, nnz_per_row=3, seed=6)
    rng = np.random.default_rng(7)
    y = rng.normal(size=80)
    g = rng.integers(0, 5, size=80).astype(np.int32)
    w = rng.normal(size=16)
    oracle = O.ShardedOracle(X, y, groups=g)
    assert oracle.name == 'sharded/csr'
    _assert_bf16_close(O.GroupedOracle(np.asarray(X.to_dense()), y, g),
                       oracle, w)
    scipy_sparse = pytest.importorskip('scipy.sparse')
    sp = scipy_sparse.csr_matrix(np.asarray(X.to_dense()))
    sp_oracle = O.ShardedOracle(sp, y, groups=g)
    assert sp_oracle.name == 'sharded/csr'
    l0, a0 = oracle.loss_and_subgrad(w)
    l1, a1 = sp_oracle.loss_and_subgrad(w)
    # the dense round-trip re-rounds the values (f64 -> f32 data), so
    # near-tie pairs may count differently: tight, not exact
    assert float(l1) == pytest.approx(float(l0), rel=1e-3, abs=1e-3)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0),
                               rtol=1e-2, atol=1e-3)


# ------------------------------------------------------ dry-run lowering


def test_bundle_dryrun_cell_lowers_without_materializing():
    mesh = make_mesh((jax.device_count(), 1), ('data', 'model'))
    shape = RankSVMShapeConfig('tiny', m=512, n=128)
    # default: the GROUPED bundle program (the production pod path)
    fn, args = O.sharded_dryrun_cell(mesh, shape, kind='bundle')
    assert len(args) == 7                     # state, X, y, g, N, lam, eps
    compiled = fn.lower(*args).compile()      # abstract args only
    assert compiled.as_text()
    fn, args = O.sharded_dryrun_cell(mesh, shape, kind='bundle',
                                     grouped=False)
    assert len(args) == 6
    assert fn.lower(*args).compile().as_text()


def test_oracle_dryrun_cell_still_available():
    mesh = make_mesh((jax.device_count(), 1), ('data', 'model'))
    shape = RankSVMShapeConfig('tiny', m=512, n=128)
    fn, args = O.sharded_dryrun_cell(mesh, shape, kind='oracle')
    assert len(args) == 4
    assert fn.lower(*args).compile().as_text()
    with pytest.raises(ValueError):
        O.sharded_dryrun_cell(mesh, shape, kind='nope')


# ------------------------------------------------------- real >1-dev mesh


@multidevice
def test_multidevice_sharded_groups_parity():
    X, y, groups, w = _grouped_case(seed=6)
    mesh = _mesh2x4()
    _assert_bf16_close(O.GroupedOracle(X, y, groups),
                       O.ShardedOracle(X, y, groups=groups, mesh=mesh), w)


@multidevice
@pytest.mark.parametrize('variant', ['base', 'opt'])
def test_multidevice_device_driver_trains(variant):
    X, y, groups, _ = _grouped_case(seed=7)
    mesh = _mesh2x4()
    oracle = O.ShardedOracle(X, y, groups=groups, mesh=mesh,
                             variant=variant)
    res = bmrm(oracle, lam=1e-2, eps=1e-2, solver='device', max_iter=200)
    assert res.stats.converged
    # the plane buffer actually lives column-sharded on the model axis
    assert res.state.A.sharding.spec == P(None, 'model')
    host = bmrm(oracle, lam=1e-2, eps=1e-2, solver='host', max_iter=200)
    # see test_sharded_host_vs_device_driver_parity: bound is eps
    assert res.stats.obj_best == pytest.approx(host.stats.obj_best,
                                               abs=1e-2)


@multidevice
def test_multidevice_row_padding_is_exact():
    """m not divisible by the mesh row axis: padded rows (own group, tied
    y, zero features) must leave the oracle value untouched."""
    rng = np.random.default_rng(10)
    m = 8 * 18 + 5                       # NOT divisible by 8 data shards
    X = rng.normal(size=(m, 8))
    y = rng.normal(size=m)
    w = rng.normal(size=8)
    mesh = make_mesh((8, 1), ('data', 'model'))
    oracle = O.ShardedOracle(X, y, mesh=mesh)
    assert oracle.m == m                 # metadata reports the REAL m
    _assert_bf16_close(O.TreeOracle(X, y), oracle, w)


@multidevice
def test_multidevice_model_axis_must_divide_n():
    rng = np.random.default_rng(11)
    X = rng.normal(size=(64, 6))         # n=6 not divisible by model=4
    y = rng.normal(size=64)
    with pytest.raises(ValueError, match='model'):
        O.ShardedOracle(X, y, mesh=_mesh2x4())


@multidevice
def test_multidevice_ungrouped_close_to_tree():
    d = cadata_like(m=256, m_test=10, seed=8)
    X = np.asarray(d.X)
    w = np.random.default_rng(8).normal(size=X.shape[1])
    _assert_bf16_close(O.TreeOracle(X, d.y),
                       O.ShardedOracle(X, d.y, mesh=_mesh2x4()), w)


@multidevice
def test_multidevice_ranksvm_sharded_end_to_end():
    d = cadata_like(m=300, m_test=100, seed=9)
    svm = RankSVM(lam=1e-2, eps=1e-2, method='sharded', mesh=_mesh2x4())
    svm.fit(np.asarray(d.X), d.y)
    assert svm.report_.solver == 'device'
    assert svm.ranking_error(d.X_test, d.y_test) < 0.35


@multidevice
def test_multidevice_path_vmap_trains():
    """Batched lambda sweep on a REAL 2x4 mesh: the vmapped bundle_step
    (leading replicated lambda axis, plane buffer column-sharded over
    'model') must train every lambda to convergence and agree with the
    sequential sweep within the bf16 tolerance."""
    X, y, groups, _ = _grouped_case(seed=7)
    svm = RankSVM(eps=1e-2, method='sharded', mesh=_mesh2x4(), max_iter=200)
    pv = svm.path(X, y, [1e-1, 1e-2], groups=groups, mode='vmap')
    ps = svm.path(X, y, [1e-1, 1e-2], groups=groups, mode='sequential')
    assert all(p.report.converged for p in pv)
    assert all(p.report.solver == 'vmap' for p in pv)
    for a, b in zip(pv, ps):
        assert a.report.objective == pytest.approx(b.report.objective,
                                                   rel=2e-2, abs=2e-3)


@multidevice
def test_multidevice_bundle_dryrun_cell():
    mesh = _mesh2x4()
    shape = RankSVMShapeConfig('tiny', m=1024, n=256)
    fn, args = O.sharded_dryrun_cell(mesh, shape, kind='bundle')
    assert fn.lower(*args).compile().as_text()
