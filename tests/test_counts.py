"""Oracle tests for the paper's core contribution: the linearithmic c/d
frequency computation (core.counts vs core.ref). The hypothesis-based
property sweeps live in test_properties.py (skipped when hypothesis is
absent); the deterministic boundary/shape cases here always run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from counts_parity import assert_counts_match as _assert_counts_match
from counts_parity import searched_counts
from repro.core import counts as C
from repro.core import ref as R


@pytest.mark.parametrize('m', [1, 2, 3, 8, 33, 128])
@pytest.mark.parametrize('tie_heavy', [False, True])
def test_counts_match_oracle_seeded(m, tie_heavy):
    rng = np.random.default_rng(m + 1000 * tie_heavy)
    if tie_heavy:
        p = (rng.integers(-2, 3, size=m) * 0.5).astype(np.float32)
        y = rng.integers(0, 3, size=m).astype(np.float32)
    else:
        p = rng.uniform(-100, 100, size=m).astype(np.float32)
        y = rng.uniform(-100, 100, size=m).astype(np.float32)
    _assert_counts_match(p, y)


def test_counts_exact_margin_boundary():
    """p_j == p_i + 1 must NOT count toward c (strict inequality in eq. 5)."""
    p = np.asarray([0.0, 1.0], np.float32)   # p_1 == p_0 + 1 exactly
    y = np.asarray([0.0, 1.0], np.float32)   # y_0 < y_1: preference pair
    c, d = _assert_counts_match(p, y)
    assert c[0] == 0 and d[1] == 0           # boundary excluded both sides


def test_counts_just_inside_margin():
    eps = np.float32(1e-3)
    p = np.asarray([0.0, 1.0 - eps], np.float32)
    y = np.asarray([0.0, 1.0], np.float32)
    c, d = _assert_counts_match(p, y)
    assert c[0] == 1 and d[1] == 1


def test_counts_empty_and_singleton():
    for m in (0, 1):
        p = np.zeros(m, np.float32)
        y = np.zeros(m, np.float32)
        c, d = searched_counts(jnp.asarray(p), jnp.asarray(y))
        assert c.shape == (m,) and d.shape == (m,)
        cf, df = C.counts_fused(jnp.asarray(p), jnp.asarray(y))
        assert cf.shape == (m,) and df.shape == (m,)


def test_counts_large_scrambled():
    rng = np.random.default_rng(7)
    m = 4097                                  # crosses a pow2 padding edge
    p = rng.normal(size=m).astype(np.float32)
    y = rng.integers(0, 50, size=m).astype(np.float32)
    c, d = searched_counts(jnp.asarray(p), jnp.asarray(y))
    cb, db = C.counts_blocked_host(jnp.asarray(p), jnp.asarray(y), block=512)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cb))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(db))
    cf, df = C.counts_fused(jnp.asarray(p), jnp.asarray(y))
    np.testing.assert_array_equal(np.asarray(cf), np.asarray(cb))
    np.testing.assert_array_equal(np.asarray(df), np.asarray(db))


def _fused_case(case, rng):
    """(p, y) for one `counts_fused` parity case; m is the case's size."""
    kind, m = case
    if kind == 'continuous':
        return rng.normal(size=m), rng.normal(size=m)
    if kind == 'tie-p':        # p +- 1 lands exactly on other scores
        return rng.integers(-6, 7, size=m) * 0.5, rng.normal(size=m)
    if kind == 'tie-y':
        return rng.normal(size=m), rng.integers(0, 4, size=m)
    if kind == 'tie-both':
        return rng.integers(-6, 7, size=m) * 0.5, rng.integers(0, 3, size=m)
    if kind == 'signed-zero':  # p_j == p_i +- 1 with p_i = -0.0 and +0.0
        p = rng.choice([-1.0, -0.0, 0.0, 1.0], size=m)
        return p, rng.choice([-0.0, 0.0, 1.0], size=m)
    if kind == 'equal-p':
        return np.full(m, 0.25), rng.integers(0, 5, size=m)
    if kind == 'equal-y':
        return rng.integers(-4, 5, size=m) * 0.5, np.full(m, 2.0)
    raise ValueError(kind)


# 3m crosses a power of two between 341/342, 1365/1366, 10922/10923 and
# 21845/21846. Blocks of more than 128 items are rows, smaller ones
# columns; the top levels, under 8 rows, sort as one flat row.
FUSED_CASES = ([('continuous', m) for m in (341, 342, 1365, 1366, 4097,
                                            10922, 10923, 21846)]
               + [(k, 1366) for k in ('tie-p', 'tie-y', 'tie-both',
                                      'signed-zero', 'equal-p', 'equal-y')]
               + [('tie-both', 10923), ('grouped', 1366), ('vmap', 342)])


@pytest.mark.parametrize('case', FUSED_CASES,
                         ids=[f'{k}-{m}' for k, m in FUSED_CASES])
def test_counts_fused_matches_blocked(case):
    """`counts_fused` equals the O(m^2) counts bit for bit: alone and under
    `jax.vmap` over a batch of score vectors (the lambda-path sweep's use)
    against `counts_blocked_host`, and through `counts_grouped_fused`'s key
    offsets against the grouped reference."""
    kind, m = case
    rng = np.random.default_rng(m + 7 * len(kind))
    if kind == 'grouped':
        p, y = _fused_case(('tie-both', m), rng)
        g = jnp.asarray(rng.integers(0, 9, size=m), jnp.int32)
        p, y = jnp.asarray(p, jnp.float32), jnp.asarray(y, jnp.float32)
        got = C.counts_grouped_fused(p, y, g)
        want = R.grouped_counts_ref(p, y, g)
    elif kind == 'vmap':
        ps = jnp.asarray(rng.integers(-6, 7, size=(4, m)) * 0.5, jnp.float32)
        y = jnp.asarray(rng.integers(0, 3, size=m), jnp.float32)
        got = jax.vmap(C.counts_fused, in_axes=(0, None))(ps, y)
        want = [np.stack(v) for v in zip(*(C.counts_blocked_host(
            q, y, block=512) for q in ps))]
    else:
        p, y = (jnp.asarray(v, jnp.float32) for v in _fused_case(case, rng))
        got = C.counts_fused(p, y)
        want = C.counts_blocked_host(p, y, block=512)
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))


@pytest.mark.parametrize('m', [300, 10923])
def test_counts_fused_lowers_without_gather_or_scatter(m):
    """The counting engine is sorts, cumsums, slices and concatenations:
    its lowered program holds no gather and no scatter (the TPU's slowest
    primitive), at two sizes."""
    x = jax.ShapeDtypeStruct((m,), jnp.float32)
    text = jax.jit(C.counts_fused).lower(x, x).as_text()
    assert 'sort' in text
    assert 'gather' not in text and 'scatter' not in text


# ------------------------------------------------------------------ groups


def test_grouped_counts_match_oracle_seeded():
    rng = np.random.default_rng(11)
    for m, n_groups in [(5, 2), (33, 3), (128, 5)]:
        p = (rng.integers(-2, 3, size=m) * 0.5).astype(np.float32)
        y = rng.integers(0, 3, size=m).astype(np.float32)
        g = rng.integers(0, n_groups, size=m).astype(np.int32)
        cr, dr = R.grouped_counts_ref(jnp.asarray(p), jnp.asarray(y),
                                      jnp.asarray(g))
        cf, df = C.counts_grouped_fused(jnp.asarray(p), jnp.asarray(y),
                                        jnp.asarray(g))
        np.testing.assert_array_equal(np.asarray(cf), np.asarray(cr))
        np.testing.assert_array_equal(np.asarray(df), np.asarray(dr))


def test_grouped_equals_global_when_one_group():
    rng = np.random.default_rng(3)
    p = rng.normal(size=64).astype(np.float32)
    y = rng.normal(size=64).astype(np.float32)
    g = np.zeros(64, np.int32)
    c0, d0 = C.counts_fused(jnp.asarray(p), jnp.asarray(y))
    cg, dg = C.counts_grouped_fused(jnp.asarray(p), jnp.asarray(y),
                                    jnp.asarray(g))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(cg))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(dg))


# ---------------------------------------------------------------- num_pairs


def test_num_pairs_seeded():
    rng = np.random.default_rng(13)
    for m in (1, 2, 33, 128):
        y = rng.integers(0, 3, size=m).astype(np.float32)
        n = float(C.num_pairs(jnp.asarray(y)))
        nr = int(R.num_pairs_ref(jnp.asarray(y)))
        nh = C.num_pairs_host(y)
        assert nh == nr
        assert n == pytest.approx(nr, rel=1e-6)


def test_num_pairs_grouped():
    y = np.asarray([0, 1, 2, 0, 1, 2], np.float32)
    g = np.asarray([0, 0, 0, 1, 1, 1], np.int32)
    n = float(C.num_pairs_grouped(jnp.asarray(y), jnp.asarray(g)))
    nr = int(R.grouped_num_pairs_ref(jnp.asarray(y), jnp.asarray(g)))
    assert n == pytest.approx(nr)
    assert nr == 6            # 3 ordered pairs in each of the two groups


# ------------------------------------------------- Joachims r-level baseline


def test_joachims_rlevel_matches_oracle_seeded():
    from repro.core import joachims as J
    rng = np.random.default_rng(17)
    for m in (2, 8, 33, 128):
        p = (rng.integers(-2, 3, size=m) * 0.5).astype(np.float32)
        y = rng.integers(0, 3, size=m).astype(np.float32)
        yl, r = J.levels_of(y)
        c, d = J.counts_rlevel(jnp.asarray(p), jnp.asarray(yl), r)
        cr, dr = R.counts_ref(jnp.asarray(p), jnp.asarray(y))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
        np.testing.assert_array_equal(np.asarray(d), np.asarray(dr))
