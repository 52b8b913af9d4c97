"""The BMRM step's named scopes and the fit's host spans.

The device chunk carries the scopes that name its work in compiled
programs and traces ('matvec', 'counts', 'rmatvec', 'plane_insert', 'qp',
and inside the tree counting 'sort', 'tree', 'query', 'unsort'); a fit
under the profiler leaves its host spans on the calling thread.
"""

import dataclasses
import glob
import re

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import bmrm as bmrm_mod
from repro.core.oracle import make_oracle
from repro.core.ranksvm import RankSVM

STEP_SCOPES = ('matvec', 'counts', 'rmatvec', 'plane_insert', 'qp')
COUNT_SCOPES = ('sort', 'tree', 'query', 'unsort')


def _features(kind, rng, m=96, n=12):
    if kind == 'dense':
        return rng.normal(size=(m, n))
    if kind == 'csr-uniform':
        s = 3
        cols = np.stack([rng.choice(n, s, replace=False) for _ in range(m)])
        indptr = np.arange(0, m * s + 1, s)
        return sp.csr_matrix((rng.normal(size=m * s), cols.ravel(), indptr),
                             shape=(m, n))
    mask = rng.random((m, n)) < 0.3
    mask[0] = True                      # rows of different lengths
    return sp.csr_matrix(np.where(mask, rng.normal(size=(m, n)), 0.0))


def _scopes_in(text):
    """Every name that a debug location gives as a scope: a `/`-separated
    component of an op's location other than its last (the primitive)."""
    out = set()
    for loc in re.findall(r'loc\("([^"]*)"', text):
        out.update(loc.split('/')[:-1])
    return out


@pytest.mark.parametrize('kind', ['dense', 'csr-uniform', 'csr-variable'])
def test_device_chunk_carries_the_step_and_counting_scopes(kind):
    rng = np.random.default_rng(7)
    X = _features(kind, rng)
    y = rng.normal(size=X.shape[0])
    oracle = make_oracle(X, y, method='tree')
    uniform = bool(getattr(oracle._feats, '_uniform', False))
    assert oracle._feats.kind == ('dense' if kind == 'dense' else 'csr')
    assert uniform == (kind == 'csr-uniform')
    state = bmrm_mod.init_bundle_state(oracle.n, 8)
    step_fn = oracle.step_fn()

    def chunk(state, lam, eps):
        return bmrm_mod._scan_chunk(step_fn, lam, eps, 4, 2, state)

    text = jax.jit(chunk).lower(state, np.float32(0.1),
                                np.float32(1e-3)).as_text(debug_info=True)
    found = _scopes_in(text)
    missing = [s for s in STEP_SCOPES + COUNT_SCOPES if s not in found]
    assert not missing, missing


def _host_line(path, span):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if any(name == span for name, _, _ in evs):
                return evs
    raise AssertionError(f'no host line holds {span!r}')


def test_a_device_fit_leaves_its_spans_on_the_calling_thread(tmp_path):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(300, 5))
    y = X @ rng.normal(size=5) + 0.1 * rng.normal(size=300)
    RankSVM(lam=0.05, solver='device', sync_every=2).fit(X, y)   # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        svm = RankSVM(lam=0.05, solver='device', sync_every=2).fit(X, y)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / '**' / '*.xplane.pb'), recursive=True)
    evs = _host_line(path, 'ranksvm.fit')

    def spans(name):
        return [(s, e) for n, s, e in evs if n == name]

    (fit,) = spans('ranksvm.fit')
    (make,) = spans('ranksvm.make_oracle')
    (solve,) = spans('ranksvm.solve')
    assert fit[0] <= make[0] <= make[1] <= solve[0] <= solve[1] <= fit[1]
    for name in ('oracle.transfer', 'oracle.pairs'):
        (s, e) = spans(name)[0]
        assert make[0] <= s <= e <= make[1]
    chunks = -(-svm.report_.iterations // 2)
    assert svm.report_.iterations >= 3
    for name in ('bmrm.dispatch', 'bmrm.sync'):
        inside = [(s, e) for s, e in spans(name)
                  if solve[0] <= s and e <= solve[1]]
        assert len(inside) == chunks == len(spans(name)), name
    # Each chunk's sync follows its dispatch.
    for (d0, d1), (s0, s1) in zip(spans('bmrm.dispatch'),
                                  spans('bmrm.sync')):
        assert d1 <= s0


def test_fit_stats_keep_one_timer_per_step():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(80, 4))
    y = rng.normal(size=80)
    oracle = make_oracle(X, y, method='tree')
    for solver in ('host', 'device'):
        stats = bmrm_mod.bmrm(oracle, lam=0.1, solver=solver,
                              max_iter=5, sync_every=2).stats
        assert len(stats.oracle_seconds) == stats.iterations
    assert [f.name for f in dataclasses.fields(bmrm_mod.BMRMStats)] == [
        'iterations', 'converged', 'obj_best', 'gap', 'loss_history',
        'gap_history', 'oracle_seconds', 'solver', 'seconds']
