"""Hypothesis property tests (counts/rank_loss/qp), collected here so the
rest of the suite still runs when the optional `hypothesis` package is
absent — this module then skips cleanly at collection time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip('hypothesis')

import hypothesis.strategies as st  # noqa: E402  (needs the importorskip)

from counts_parity import assert_counts_match as _assert_counts_match  # noqa: E402
from repro.core import counts as C  # noqa: E402
from repro.core import rank_loss as RL  # noqa: E402
from repro.core import ref as R  # noqa: E402
from repro.core.qp import project_simplex  # noqa: E402

# bounded shape set -> bounded number of jit recompiles under hypothesis
_SIZES = (1, 2, 3, 8, 33, 128)


@st.composite
def _py_arrays(draw, tie_heavy: bool):
    m = draw(st.sampled_from(_SIZES))
    if tie_heavy:
        # few distinct values in both p and y -> lots of boundary cases
        pv = draw(st.lists(st.integers(-2, 2), min_size=m, max_size=m))
        yv = draw(st.lists(st.integers(0, 2), min_size=m, max_size=m))
        p = np.asarray(pv, np.float32) * 0.5
        y = np.asarray(yv, np.float32)
    else:
        fin = st.floats(-100, 100, allow_nan=False, allow_subnormal=False,
                        width=32)
        p = np.asarray(draw(st.lists(fin, min_size=m, max_size=m)),
                       np.float32)
        y = np.asarray(draw(st.lists(fin, min_size=m, max_size=m)),
                       np.float32)
    return p, y


@hypothesis.given(_py_arrays(tie_heavy=False))
@hypothesis.settings(max_examples=40, deadline=None)
def test_counts_match_oracle_random(py):
    _assert_counts_match(*py)


@hypothesis.given(_py_arrays(tie_heavy=True))
@hypothesis.settings(max_examples=40, deadline=None)
def test_counts_match_oracle_tie_heavy(py):
    """Ties in p AND y exercise the strict/non-strict boundary semantics
    (the margin conditions p_j < p_i + 1 are strict, y comparisons strict)."""
    _assert_counts_match(*py)


@hypothesis.given(_py_arrays(tie_heavy=True))
@hypothesis.settings(max_examples=25, deadline=None)
def test_sum_c_equals_sum_d(py):
    """Invariant: sum_i c_i == sum_i d_i (pair (i,j) is counted once from
    each side — relabelling symmetry of eqs. (5)/(6)).

    Holds EXACTLY only when p ± 1 is exact in fp (here: multiples of 0.5):
    for general floats the paper's own eqs. (5)/(6) evaluate `p_i + 1` and
    `p_j - 1` with different roundings, so the two sums can differ by the
    pairs that land inside one ulp of the margin — a property of the
    equations, not of our implementation (which matches the oracle
    bit-for-bit either way; hypothesis found the counterexample)."""
    c, d = _assert_counts_match(*py)
    assert c.sum() == d.sum()


@hypothesis.given(_py_arrays(tie_heavy=True), st.integers(1, 5))
@hypothesis.settings(max_examples=30, deadline=None)
def test_grouped_counts_match_oracle(py, n_groups):
    p, y = py
    rng = np.random.default_rng(len(p))
    g = rng.integers(0, n_groups, size=len(p)).astype(np.int32)
    cr, dr = R.grouped_counts_ref(jnp.asarray(p), jnp.asarray(y),
                                  jnp.asarray(g))
    cf, df = C.counts_grouped_fused(jnp.asarray(p), jnp.asarray(y),
                                    jnp.asarray(g))
    np.testing.assert_array_equal(np.asarray(cf), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(df), np.asarray(dr))


@hypothesis.given(_py_arrays(tie_heavy=True))
@hypothesis.settings(max_examples=30, deadline=None)
def test_num_pairs(py):
    _, y = py
    n = float(C.num_pairs(jnp.asarray(y)))
    nr = int(R.num_pairs_ref(jnp.asarray(y)))
    nh = C.num_pairs_host(y)
    assert nh == nr
    assert n == pytest.approx(nr, rel=1e-6)


@hypothesis.given(_py_arrays(tie_heavy=True))
@hypothesis.settings(max_examples=25, deadline=None)
def test_joachims_rlevel_matches_oracle(py):
    """The paper's main baseline (SVM^rank's O(rm) counts) must agree with
    the oracle — and with the tree method — on any tie pattern."""
    from repro.core import joachims as J
    p, y = py
    yl, r = J.levels_of(y)
    c, d = J.counts_rlevel(jnp.asarray(p), jnp.asarray(yl), r)
    cr, dr = R.counts_ref(jnp.asarray(p), jnp.asarray(y))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dr))


# ----------------------------------------------------------------- rank_loss


@st.composite
def _scores_utils(draw):
    m = draw(st.sampled_from((2, 3, 17, 64)))
    # allow_subnormal=False: XLA flushes denormals to zero, numpy doesn't
    fin = st.floats(-10, 10, allow_nan=False, allow_subnormal=False,
                    width=32)
    p = np.asarray(draw(st.lists(fin, min_size=m, max_size=m)), np.float32)
    y = np.asarray(draw(st.lists(st.integers(0, 3), min_size=m, max_size=m)),
                   np.float32)
    hypothesis.assume(len(np.unique(y)) > 1)      # need >= 1 preference pair
    return p, y


@hypothesis.given(_scores_utils())
@hypothesis.settings(max_examples=30, deadline=None)
def test_loss_matches_bruteforce(py):
    p, y = py
    loss = RL.pairwise_hinge_loss(jnp.asarray(p), jnp.asarray(y))
    ref = R.loss_ref(jnp.asarray(p), jnp.asarray(y))
    assert float(loss) == pytest.approx(float(ref), rel=1e-5, abs=1e-6)


@hypothesis.given(_scores_utils())
@hypothesis.settings(max_examples=20, deadline=None)
def test_vjp_is_lemma2_subgradient(py):
    """The custom VJP must equal (c - d)/N (Lemma 2, wrt scores)."""
    p, y = py
    g = jax.grad(lambda s: RL.pairwise_hinge_loss(s, jnp.asarray(y)))(
        jnp.asarray(p))
    c, d = R.counts_ref(jnp.asarray(p), jnp.asarray(y))
    n = max(int(R.num_pairs_ref(jnp.asarray(y))), 1)
    expect = (np.asarray(c) - np.asarray(d)) / n
    np.testing.assert_allclose(np.asarray(g), expect, rtol=1e-6)


def _brute_rank_error(p, y, g=None):
    m = len(p)
    tot, n = 0.0, 0
    for i in range(m):
        for j in range(m):
            if (g is None or g[i] == g[j]) and y[i] < y[j]:
                n += 1
                if p[i] > p[j]:
                    tot += 1.0
                elif p[i] == p[j]:
                    tot += 0.5
    return tot / max(n, 1)


@hypothesis.given(_scores_utils())
@hypothesis.settings(max_examples=20, deadline=None)
def test_ranking_error_matches_bruteforce(py):
    p, y = py
    err = RL.ranking_error(jnp.asarray(p), jnp.asarray(y))
    assert float(err) == pytest.approx(_brute_rank_error(p, y), abs=1e-5)


# ---------------------------------------------------------------- loss axis


from oracle_ref import LOSS_REFS  # noqa: E402
from repro.core import oracle as O  # noqa: E402

_LOSSES = tuple(LOSS_REFS)


def _fused_at(loss, p, y, g):
    """(R_emp, normalized subgrad wrt scores) via the fused counting core
    every oracle reduces to (`oracle._loss_and_coeffs`)."""
    norm, v = O._loss_norm_weights(y, g, loss)
    inv_n = np.float32(0.0 if norm == 0 else 1.0 / norm)
    vv = None if v is None else jnp.asarray(v, jnp.float32)
    gi = None if g is None else jnp.asarray(g, jnp.int32)
    val, cd = O._loss_and_coeffs(jnp.asarray(p), jnp.asarray(y), gi,
                                 inv_n, vv, loss=loss)
    return float(val), np.asarray(cd, np.float64) * float(inv_n)


@st.composite
def _loss_case(draw):
    """Tie-heavy quantized (p, q, y, g): scores on the 0.5 grid are exact
    in f32, so f32-vs-f64 tie-breaks are deterministic (the property the
    differential suite's fit cases rely on, stressed here with far more
    adversarial draws). q is a second score vector for tangent checks."""
    m = draw(st.sampled_from(_SIZES))
    ints = st.lists(st.integers(-2, 2), min_size=m, max_size=m)
    p = np.asarray(draw(ints), np.float32) * 0.5
    q = np.asarray(draw(ints), np.float32) * 0.5
    y = np.asarray(draw(st.lists(st.integers(0, 2), min_size=m,
                                 max_size=m)), np.float32)
    g = np.sort(np.asarray(draw(st.lists(st.integers(0, 2), min_size=m,
                                         max_size=m)), np.int32))
    return p, q, y, g


@pytest.mark.parametrize('loss', ('toppush', 'poshinge'))
@hypothesis.given(_loss_case(), st.booleans())
@hypothesis.settings(max_examples=30, deadline=None)
def test_new_loss_fused_matches_ref(loss, case, grouped):
    """Fused core vs the plain-numpy brute force (`oracle_ref`) — loss
    AND the exact subgradient element, tie-break included."""
    p, _, y, g = case
    g = g if grouped else None
    got_l, got_sub = _fused_at(loss, p, y, g)
    ref_l, ref_sub = LOSS_REFS[loss](p, y, g)
    tol = 1e-5 if loss == 'toppush' else 5e-5
    assert got_l == pytest.approx(ref_l, rel=tol, abs=tol)
    np.testing.assert_allclose(got_sub, ref_sub, rtol=tol, atol=tol)


@pytest.mark.parametrize('loss', _LOSSES)
@hypothesis.given(_loss_case())
@hypothesis.settings(max_examples=25, deadline=None)
def test_loss_plane_is_lower_tangent(loss, case):
    """BMRM's correctness hinges on every cutting plane under-estimating
    the risk: for convex R and subgradient s at p, the tangent
    R(p) + s·(q - p) must lower-bound R(q) at ANY q."""
    p, q, y, g = case
    r_p, sub = _fused_at(loss, p, y, g)
    r_q, _ = _fused_at(loss, q, y, g)
    plane = r_p + sub @ (np.asarray(q, np.float64)
                         - np.asarray(p, np.float64))
    assert plane <= r_q + 1e-5


# ------------------------------------------------------------------ simplex


@hypothesis.given(st.lists(st.floats(-5, 5, allow_nan=False, width=32),
                           min_size=1, max_size=20))
@hypothesis.settings(max_examples=50, deadline=None)
def test_project_simplex_properties(vals):
    x = project_simplex(np.asarray(vals, np.float64))
    assert np.all(x >= 0)
    assert np.sum(x) == pytest.approx(1.0, abs=1e-9)
