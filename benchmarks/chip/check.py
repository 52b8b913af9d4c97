"""The comparison that decides `correct`: the models a window produced,
against the plain reference (`references/<loss>.py`), at the timed sizes.

For each model checked it reads, each as a relative error or a ratio:

  loss_rel     the value of each cutting plane at the iterate it was cut
               at, b_i + <a_i, s_i>, against the reference loss R(s_i): the
               score matvec, the counting pass and the loss;
  grad_rel     each plane's gradient a_i against the reference subgradient
               at s_i: the counting pass and the transpose product;
  obj_rel      the reported objective J(w) against R(w) + lam |w|^2 at the
               model's weights;
  w_rel        the last iterate against -A^T alpha / (2 lam) from the
               bundle's own planes and dual: the BMRM update;
  simplex_err  how far the bundle dual alpha lies off the simplex
               (|sum alpha - 1|, or its most negative entry);
  dual_rel     the dual value the program stored, J(w_best) - gap, against
               D(alpha) = b.alpha - |A^T alpha|^2 / (4 lam) recomputed in
               float64 from the bundle, as a share of J(w_best);
  qp_rel       how far D(alpha) falls short of the bundle dual's maximum
               over the simplex (float64), as a share of J(w_best): the QP;
  stop_gap     for a model the job fitted to eps, the reference's gap
               R(w_best) + lam |w_best|^2 - D(alpha) over eps: the stop.
               A model that was due at eps and did not get there reads inf.

Each number is the largest over the planes and models checked. A model
with no plane, or a window with no model, is not correct.
"""

from __future__ import annotations

import math

import numpy as np

import spec

NUMBERS = ('loss_rel', 'grad_rel', 'obj_rel', 'w_rel', 'simplex_err',
           'dual_rel', 'qp_rel', 'stop_gap')


def _rel(x: float, ref: float) -> float:
    return abs(x - ref) / max(abs(ref), 1e-30)


def project_simplex(v: np.ndarray) -> np.ndarray:
    """Euclidean projection onto {x >= 0, sum x = 1} (Duchi et al. 2008)."""
    u = np.sort(v)[::-1]
    css = np.cumsum(u) - 1.0
    k = np.nonzero(u * np.arange(1, v.size + 1) > css)[0][-1]
    return np.maximum(v - css[k] / (k + 1.0), 0.0)


def dual_value(A, b, lam: float, alpha) -> float:
    """D(alpha) = b.alpha - |A^T alpha|^2 / (4 lam), in float64."""
    v = A.T @ alpha
    return float(b @ alpha - (v @ v) / (4.0 * lam))


def dual_max(A, b, lam: float, alpha0=None, iters: int = 3000) -> tuple:
    """(alpha, D(alpha)) maximising the bundle dual over the simplex:
    float64 accelerated projected gradient from `alpha0` (uniform if
    None), the best iterate kept."""
    G = A @ A.T
    L = max(float(np.linalg.eigvalsh(G)[-1]) / (2.0 * lam), 1e-300)
    x = (np.full(b.size, 1.0 / b.size) if alpha0 is None
         else project_simplex(np.asarray(alpha0, np.float64)))

    def value(a):
        return float(b @ a - a @ G @ a / (4.0 * lam))

    best = (value(x), x)
    z, t = x.copy(), 1.0
    for _ in range(iters):
        x_new = project_simplex(z + (b - G @ z / (2.0 * lam)) / L)
        t_new = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * t * t))
        z = x_new + ((t - 1.0) / t_new) * (x_new - x)
        x, t = x_new, t_new
        f = value(x)
        if f > best[0]:
            best = (f, x)
    return best[1], best[0]


def measure_one(rec, ref, rng, max_planes: int) -> dict:
    """The numbers of one model; at most `max_planes` planes of its bundle,
    drawn with `rng`, the last slot always among them."""
    out = {}
    r_w, _ = ref.loss_and_subgrad(rec.w_best)
    j_ref = r_w + rec.lam * float(rec.w_best @ rec.w_best)
    out['obj_rel'] = _rel(rec.objective, j_ref)
    if rec.A is None:
        return out
    P = rec.A.shape[0]
    if P == 0:
        return {k: math.inf for k in NUMBERS}
    pick = set(rng.choice(P, size=min(max_planes, P),
                          replace=False).tolist())
    if P - 1 not in pick:
        pick.pop()
        pick.add(P - 1)
    out['loss_rel'] = out['grad_rel'] = 0.0
    for i in sorted(pick):
        r_i, a_i = ref.loss_and_subgrad(rec.S[i])
        value = rec.b[i] + float(rec.A[i] @ rec.S[i])
        out['loss_rel'] = max(out['loss_rel'], _rel(value, r_i))
        out['grad_rel'] = max(out['grad_rel'], float(
            np.linalg.norm(rec.A[i] - a_i)
            / max(np.linalg.norm(a_i), 1e-30)))
    w_q = -(rec.A.T @ rec.alpha) / (2.0 * rec.lam)
    out['w_rel'] = float(np.linalg.norm(rec.w - w_q)
                         / max(np.linalg.norm(w_q), 1e-30))

    scale = max(abs(j_ref), 1e-30)
    out['simplex_err'] = max(abs(float(np.sum(rec.alpha)) - 1.0),
                             -float(np.min(rec.alpha)))
    d_alpha = dual_value(rec.A, rec.b, rec.lam, rec.alpha)
    out['dual_rel'] = abs((rec.objective - rec.gap) - d_alpha) / scale
    _, d_max = dual_max(rec.A, rec.b, rec.lam, rec.alpha)
    out['qp_rel'] = max(d_max - d_alpha, 0.0) / scale
    if rec.must_converge:
        out['stop_gap'] = ((j_ref - d_alpha) / rec.eps if rec.done
                           else math.inf)
    return out


def reference_class(loss: str):
    """The plain reference of `loss`: `references/<loss>.py`."""
    return spec.load_module('references', loss).Reference


def measure(records: list, problems: list, max_planes: int,
            seed: int, loss: str = 'hinge') -> tuple:
    """(the largest of each number over `records`, each record's numbers),
    each record against the reference on its data set,
    `problems[record.problem]`. A number that no record gives is left out
    (and `verdict` reads it as failed where the cell holds it)."""
    Reference = reference_class(loss)
    refs = {}
    rng = np.random.default_rng(seed)
    each = []
    for rec in records:
        if rec.problem not in refs:
            data = problems[rec.problem]
            refs[rec.problem] = Reference(data.X, data.y, data.groups)
        each.append(measure_one(rec, refs[rec.problem], rng, max_planes))
    return {k: max(e[k] for e in each if k in e) for k in NUMBERS
            if any(k in e for e in each)}, each


def failures(each: list, limits: dict) -> int:
    """How many records have a number past its limit."""
    return sum(any(not (e[k] <= limits[k]) for k in e if k in limits)
               for e in each)


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {'value', 'limit'}}) for the numbers with limits."""
    checks = {k: {'value': float(numbers.get(k, math.inf)),
                  'limit': limits[k]}
              for k in NUMBERS if k in limits}
    ok = bool(checks) and all(
        math.isfinite(c['value']) and c['value'] <= c['limit']
        for c in checks.values())
    return ok, checks
