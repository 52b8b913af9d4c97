"""The plain reference against the definitions, pair by pair, with and
without query groups."""

import numpy as np
import pytest

import check


def _brute(X, y, g, w):
    p = X @ w
    m = len(y)
    loss, coef, n = 0.0, np.zeros(m), 0
    for i in range(m):
        for j in range(m):
            if g[i] == g[j] and y[i] < y[j]:
                n += 1
                if 1.0 + p[i] - p[j] > 0:
                    loss += 1.0 + p[i] - p[j]
                    coef[i] += 1.0
                    coef[j] -= 1.0
    return loss / n, X.T @ coef / n, n


@pytest.mark.parametrize('grouped', [False, True])
def test_hinge_reference_matches_the_pairwise_definition(grouped):
    rng = np.random.default_rng(7)
    m, n = 61, 5
    X = np.round(rng.normal(size=(m, n)) * 4) / 4
    y = rng.integers(0, 6, m).astype(np.float64)      # many ties
    g = rng.integers(0, 4, m) if grouped else np.zeros(m, np.int64)
    w = np.round(rng.normal(size=n) * 4) / 4
    ref = check.reference_class('hinge')(X, y, g if grouped else None,
                                         block=16)
    loss, grad, n_pairs = _brute(X, y, g, w)
    r, a = ref.loss_and_subgrad(w)
    assert ref.n_pairs == n_pairs
    assert r == pytest.approx(loss, rel=1e-6)
    np.testing.assert_allclose(a, grad, rtol=1e-12, atol=1e-12)


def test_dual_max_reaches_the_bundle_optimum():
    rng = np.random.default_rng(3)
    A = rng.normal(size=(7, 20))
    b = rng.normal(size=7)
    alpha, d = check.dual_max(A, b, 0.3)
    assert abs(alpha.sum() - 1.0) < 1e-12 and alpha.min() >= 0.0
    assert d == pytest.approx(check.dual_value(A, b, 0.3, alpha))
    # No vertex nor random point of the simplex does better.
    for x in list(np.eye(7)) + list(rng.dirichlet(np.ones(7), 200)):
        assert check.dual_value(A, b, 0.3, x) <= d + 1e-12
