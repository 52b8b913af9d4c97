"""The generators: deterministic in the seed, and shaped as the
configurations say."""

import json
import os

import numpy as np
import pytest

import gen

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'configs')
BIG_SEED = 2 ** 33 + 12345


def _config(name, **cut):
    with open(os.path.join(CONFIGS, name + '.json')) as f:
        cfg = json.load(f)
    cfg.update(cut)
    return cfg


@pytest.fixture(scope='module')
def rcv1():
    # The published width (n = 47,236) and row-length law; fewer rows.
    return gen.generate(_config('rcv1-sim', m=20000, nnz=20000 * 74),
                        BIG_SEED)


@pytest.mark.parametrize('name,cut', [('rcv1-sim', {'m': 3000,
                                                      'nnz': 3000 * 74}),
                                      ('cadata', {})])
def test_same_seed_same_data_other_seed_other_data(name, cut):
    a = gen.generate(_config(name, **cut), BIG_SEED)
    b = gen.generate(_config(name, **cut), BIG_SEED)
    c = gen.generate(_config(name, **cut), BIG_SEED + 1)
    np.testing.assert_array_equal(a.y, b.y)
    assert not np.array_equal(a.y, c.y)
    if name == 'rcv1-sim':
        for k in ('data', 'indices', 'indptr'):
            np.testing.assert_array_equal(getattr(a.X, k), getattr(b.X, k))
    else:
        np.testing.assert_array_equal(a.X, b.X)


def test_rcv1_rows_vary_in_length_and_fill_the_stated_nnz(rcv1):
    lens = np.diff(rcv1.X.indptr)
    assert lens.sum() == 20000 * 74
    assert lens.std() > 20.0 and lens.min() < 30 and lens.max() > 300
    assert rcv1.X.shape == (20000, 47236)


def test_rcv1_rows_are_canonical_and_normalised(rcv1):
    X = rcv1.X
    rows = X.row_ids()
    keys = rows * X.shape[1] + X.indices
    assert np.all(np.diff(keys) > 0)            # sorted, distinct columns
    norms = np.bincount(rows, weights=X.data ** 2)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-12)


def test_rcv1_utilities_are_nearly_all_distinct(rcv1):
    assert np.unique(rcv1.y).size >= 0.99 * rcv1.y.size


def test_rcv1_takes_the_program_s_non_uniform_csr_branch():
    from repro.core.oracle import _CSRFeatures
    data = gen.generate(_config('rcv1-sim', m=2000, nnz=2000 * 74),
                        BIG_SEED)
    assert _CSRFeatures(data.X)._uniform is False


def test_cadata_ties_its_top_share_at_one_cap_value():
    cfg = _config('cadata')
    data = gen.generate(cfg, BIG_SEED)
    assert data.X.shape == (20640, 8)
    tied = int(np.sum(data.y == data.y.max()))
    assert tied == round(cfg['capped_share'] * cfg['m'])
    assert np.unique(data.y).size == data.y.size - tied + 1


def test_cadata_runs_fit_the_same_problems_in_a_seeded_order():
    import job
    cfg = _config('cadata', m=500)
    a = job.make(cfg, {'job': 'fits'}, BIG_SEED)
    b = job.make(cfg, {'job': 'fits'}, BIG_SEED + 1)
    a.generate()
    b.generate()
    assert sorted(a.order) == sorted(b.order) == list(range(cfg['problems']))
    assert a.order != b.order
    for pa, pb in zip(a.problems, b.problems):
        np.testing.assert_array_equal(pa.y, pb.y)
