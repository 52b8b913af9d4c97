"""Bundle Method for Regularized Risk Minimization — Algorithm 1 of the paper.

Loss-agnostic cutting-plane optimizer for  J(w) = R_emp(w) + lam * ||w||^2.
Follows Teo et al. (2010) with the Franc & Sonnenburg (2009) best-iterate rule
the paper adopts: w_b tracks the best J seen; the gap eps_t = J(w_b) - J_t(w_t)
is the termination statistic (it upper-bounds J(w_b) - J(w*)).

This module is a solver LAYER with two interchangeable drivers behind the
single entry point `bmrm(..., solver=)`:

* **host driver** (`solver='host'`) — the float64 reference path. One oracle
  call per Python-loop turn; the plane matrix A follows the oracle onto the
  device when it is device-resident, but the Gram bookkeeping, the bundle
  dual QP (`qp.solve_bundle_dual`, float64 FISTA) and every scalar decision
  run on host. Works with bare `w -> (R_emp, a)` callables.

* **device driver** (`solver='device'`) — the whole iteration is ONE jitted
  `bundle_step` (DESIGN.md §4): fused oracle step -> plane insert into a
  preallocated (max_planes, n) buffer via `dynamic_update_slice` ->
  incremental Gram row/col update -> fixed-iteration masked FISTA QP
  (`qp.solve_bundle_dual_jax`) -> w_t update -> duality-gap statistic.
  Steps are chunked `sync_every` at a time through `lax.scan`, and the
  Python loop syncs only a handful of scalars per chunk — per `sync_every`
  oracle calls exactly one host<->device round-trip happens, instead of the
  host driver's several-per-iteration. `sync_every='auto'` retunes the
  chunk length between chunks from the observed gap-decay rate. Requires
  an oracle exposing a traced `step_fn` (`core.oracle._FusedOracle`, the
  mesh `ShardedOracle` — which also annotates the `BundleState` with
  shardings via `bundle_state_shardings`, keeping the plane buffer
  column-sharded over 'model' across chunks — or the out-of-core
  `StreamingOracle`, whose step_fn pulls feature row blocks through
  `jax.pure_callback` inside the traced scan: the chunking amortizes the
  driver's dispatch the same way, and only O(block·n) of features is ever
  device-resident). All bundle state is f32; the
  gap uses the DUAL value D(alpha) (not the primal J_t(w_t)), so a
  not-fully-converged inner QP can only over-estimate the gap — never a
  premature convergence claim.

`solver='auto'` picks the device driver whenever the oracle supports it
(`supports_device_solver`), measures as profitable for its layout/backend
(`prefer_device_solver` — e.g. CPU CSR oracles with a host-dispatched
transpose-matvec stay on the host driver), and `eps` is above the f32
noise floor; else it falls back to host.

The fixed-capacity `BundleState` is also the unit of warm-starting:
`bmrm(..., state=prev.state)` re-enters the driver with the previous run's
cutting planes, which the sequential regularization-path sweep uses —
the planes under-estimate R_emp independently of lam, so they stay valid
cuts when lam changes and only the scalar statistics reset.

**Batched path sweep** (`bmrm_path`, DESIGN.md §7): since lam enters the
jitted `bundle_step` only as a traced scalar, a whole regularization path
can run as ONE device program — `bmrm_path(oracle, lams, mode='vmap')`
carries a (K, ...)-leading `BundleState` (one slice per lambda) through
the same chunked `lax.scan`, vmapping the fused oracle step and the
masked FISTA QP over the lambda axis. Each lambda keeps its own
convergence gap and done flag; a converged lambda's state is frozen by a
per-lambda done mask (its slice stops changing — a no-op, not a barrier)
and the chunk loop exits when every lambda is done. `mode='sequential'`
is the warm-started loop described above; `mode='auto'` picks vmap for
oracles that support it (`supports_path_vmap`) on accelerator backends
when the projected K-scaled state fits `memory_budget` — the serial CPU
backend measures 2-8x slower batched (EXPERIMENTS §Path sweep) and
stays sequential, and an over-budget projection falls back to
sequential with a loud warning (the K·n plane-buffer memory trade is
real: `path_state_gib`).
"""

from __future__ import annotations

import dataclasses
import math
import time
import warnings
import weakref
from typing import Callable, NamedTuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .qp import solve_bundle_dual, solve_bundle_dual_jax

f32 = jnp.float32

# Below this eps the f32 device bundle state's ~1e-6-relative noise floor
# can stall the gap; 'auto' falls back to the float64 host driver.
F32_EPS_FLOOR = 1e-5

# sync_every='auto' schedule: start small for fast gap feedback, then pick
# the next chunk from the observed gap-decay rate. Chunk lengths are powers
# of two so the jitted-chunk cache stays at <= 6 compiled programs.
AUTO_SYNC_INIT = 4
AUTO_SYNC_MAX = 32

# Default plane capacity of the device driver's fixed buffers. BMRM on the
# ranking losses here converges in tens of iterations, and past capacity
# the least-active plane is overwritten (convergence is preserved, Teo et
# al. sec. 5). Measured on the CPU backend the masked QP cost rises with K
# even when few planes are active (the K-sized simplex projection sort is
# the term), so the default stays close to the typical active count.
DEFAULT_MAX_PLANES = 64

SOLVERS = ('host', 'device', 'auto')


@dataclasses.dataclass
class BMRMStats:
    iterations: int
    converged: bool
    obj_best: float
    gap: float
    loss_history: list
    gap_history: list
    oracle_seconds: list  # host: per-iteration oracle wall time;
    # device: the chunk's wall time (dispatch, its fused steps of oracle,
    # plane insert and QP, and the host sync that reads its results)
    # amortized over the chunk's steps. Either way wall-clock truth: on a
    # cold fit the first entry (host) / first chunk's entries (device)
    # include one-time jit trace+compile — warm the oracle (or compare
    # second fits, as the benchmarks do) for steady-state numbers.
    solver: str = 'host'
    seconds: float = float('nan')  # wall-clock of the fit; filled by
    # `bmrm_path` (for mode='vmap' each lambda gets its share of the one
    # joint program: every batched step's wall splits evenly over the
    # lambdas active in it, so seconds == sum(oracle_seconds) and the
    # per-lambda values sum to ~the sweep's wall-clock)


@dataclasses.dataclass
class BMRMResult:
    w: np.ndarray
    stats: BMRMStats
    state: 'BundleState | None' = None   # device driver: warm-startable


# ---------------------------------------------------------------- dispatch


def bmrm(loss_and_subgrad: Union[Callable, object],
         dim: int | None = None,
         lam: float = 1e-3,
         eps: float = 1e-3,
         max_iter: int = 1000,
         w0: np.ndarray | None = None,
         max_planes: int | None = None,
         callback: Callable | None = None,
         solver: str = 'auto',
         sync_every: 'int | str' = 8,
         qp_iters: int = 128,
         state: 'BundleState | None' = None) -> BMRMResult:
    """Minimize R_emp(w) + lam ||w||^2 by cutting planes.

    One lambda per call; `bmrm_path` sweeps a whole regularization path
    (sequentially warm-started or as one batched vmapped program).

    Args:
      loss_and_subgrad: w -> (R_emp(w), subgradient of R_emp at w), or a
        RankOracle (anything exposing `.loss_and_subgrad` and `.n`).
      dim: dimensionality of w; defaults to `oracle.n` for RankOracles.
      lam: regularization constant (the paper's lambda), default 1e-3.
      eps: termination gap (default 1e-3, the paper's/SVM^rank's).
        Below F32_EPS_FLOOR = 1e-5 the f32 device bundle state's
        ~1e-6-relative noise floor can stall the gap: solver='auto'
        falls back to the float64 host driver there, and an explicit
        solver='device' warns.
      max_iter: iteration cap (the device driver rounds up to a whole
        number of `sync_every`-sized chunks).
      w0: optional warm start.
      max_planes: cap on retained planes. Host: optional, oldest-inactive
        dropped past the cap (Teo et al. sec. 5). Device: the static buffer
        capacity, defaulting to DEFAULT_MAX_PLANES; past it the
        smallest-dual-weight plane is overwritten in place.
      solver: 'host' | 'device' | 'auto' (see module docstring).
      sync_every: device driver: oracle steps fused per jitted chunk; the
        host syncs one scalar set per chunk. Higher amortizes dispatch
        further but can overshoot convergence by up to sync_every-1 steps.
        'auto' tunes the chunk length per chunk from the observed gap-decay
        rate: long chunks while the predicted steps-to-eps is large, short
        ones near convergence, bounding the overshoot to about half the
        predicted remaining work (ROADMAP sync autotuning).
      qp_iters: device driver: fixed FISTA iterations of the on-device
        bundle dual solve.
      state: device driver: warm-start bundle state from a previous
        BMRMResult (regularization-path reuse; planes are kept, scalar
        statistics reset).
    """
    if solver not in SOLVERS:
        raise ValueError(f'unknown solver {solver!r}; expected one of '
                         f'{SOLVERS}')
    if isinstance(sync_every, str) and sync_every != 'auto':
        raise ValueError(f"unknown sync_every {sync_every!r}; expected an "
                         "int or 'auto'")
    oracle = (loss_and_subgrad
              if hasattr(loss_and_subgrad, 'loss_and_subgrad') else None)
    fn = oracle.loss_and_subgrad if oracle is not None else loss_and_subgrad
    if dim is None:
        if oracle is None:
            raise ValueError('dim is required for bare-callable oracles')
        dim = int(oracle.n)
    device_capable = bool(oracle is not None
                          and getattr(oracle, 'supports_device_solver',
                                      False))
    if solver == 'device':
        if not device_capable:
            raise ValueError(
                "solver='device' needs an oracle with a traced step_fn "
                '(core.oracle fused oracles); got '
                f'{type(loss_and_subgrad).__name__}')
        use_device = True
    else:
        use_device = (solver == 'auto' and device_capable
                      and getattr(oracle, 'prefer_device_solver', True)
                      and eps >= F32_EPS_FLOOR)
    if use_device and eps < F32_EPS_FLOOR:
        warnings.warn(f'eps={eps:g} is below the f32 noise floor of the '
                      'device bundle state; the gap may stall above it',
                      RuntimeWarning, stacklevel=2)
    if use_device:
        return _bmrm_device(oracle, dim=dim, lam=lam, eps=eps,
                            max_iter=max_iter, w0=w0, max_planes=max_planes,
                            callback=callback, sync_every=sync_every,
                            qp_iters=qp_iters, state=state)
    if state is not None:
        raise ValueError('bundle-state warm starts require the device '
                         "driver; pass solver='device' or w0=")
    device_arrays = bool(oracle is not None
                         and getattr(oracle, 'device_resident', False))
    return _bmrm_host(fn, dim=dim, device=device_arrays, lam=lam, eps=eps,
                      max_iter=max_iter, w0=w0, max_planes=max_planes,
                      callback=callback)


# ------------------------------------------------------------- host driver


def _bmrm_host(fn, dim, device, lam, eps, max_iter, w0, max_planes,
               callback) -> BMRMResult:
    """Float64 reference driver: one oracle call per Python-loop turn.

    `fn` and `dim` arrive resolved by the `bmrm` dispatcher; `device` says
    whether fn is a device-resident oracle step (the plane matrix then
    follows it onto the device).
    """
    if device and eps < F32_EPS_FLOOR:
        # Device oracles return f32 subgradients and the plane bookkeeping
        # stays f32 on device; the duality gap then carries an ~1e-6-relative
        # noise floor and may stall above very tight eps (bare callables keep
        # the pre-refactor float64 path and are unaffected).
        warnings.warn(f'eps={eps:g} is below the f32 noise floor of '
                      'device-resident oracles; the gap may stall above it',
                      RuntimeWarning, stacklevel=3)

    if device:
        w_prev = (jnp.zeros(dim, jnp.float32) if w0 is None
                  else jnp.asarray(w0, jnp.float32))
        A = jnp.zeros((0, dim), jnp.float32)   # plane gradients, on device
    else:
        w_prev = np.zeros(dim) if w0 is None else np.asarray(w0, np.float64)
        A = np.zeros((0, dim))

    bvec = np.zeros((0,))         # offsets b_i            (host, tiny)
    G = np.zeros((0, 0))          # Gram matrix A A'       (host, t x t)
    alpha = None

    # J at the starting point (evaluated inside the first loop turn).
    w_best = w_prev if device else w_prev.copy()
    j_best = np.inf
    stats = BMRMStats(0, False, np.inf, np.inf, [], [], [],
                      solver='host')

    for t in range(1, max_iter + 1):
        t0 = time.perf_counter()
        r_emp, a_t = fn(w_prev)
        r_emp = float(r_emp)      # blocks on the fused device step
        stats.oracle_seconds.append(time.perf_counter() - t0)

        a_t = (jnp.asarray(a_t, jnp.float32) if device
               else np.asarray(a_t, np.float64))
        wa = float(w_prev @ a_t)
        ww = float(w_prev @ w_prev)
        a_sq = float(a_t @ a_t)
        cross = (np.asarray(A @ a_t, np.float64) if len(A)
                 else np.zeros((0,)))
        A = (jnp.concatenate([A, a_t[None, :]], axis=0) if device
             else np.vstack([A, a_t[None, :]]))

        j_prev = r_emp + lam * ww
        if j_prev < j_best:
            j_best, w_best = j_prev, (w_prev if device else w_prev.copy())

        bvec = np.append(bvec, r_emp - wa)
        Gn = np.empty((len(bvec), len(bvec)))
        Gn[:-1, :-1] = G
        Gn[-1, :-1] = cross
        Gn[:-1, -1] = cross
        Gn[-1, -1] = a_sq
        G = Gn

        if max_planes is not None and len(bvec) > max_planes:
            # Drop the plane with the smallest dual weight (least active).
            # `alpha` is the previous solve's dual — length len(bvec)-1, it
            # does not yet cover the plane appended above (which is never
            # the drop candidate: it's untested, not inactive).
            drop = int(np.argmin(alpha)) if alpha is not None else 0
            keep = np.ones(len(bvec), bool)
            keep[drop] = False
            if alpha is not None:
                alpha = alpha[keep[:-1]]
                s = alpha.sum()
                alpha = alpha / s if s > 0 else None
            bvec, G = bvec[keep], G[np.ix_(keep, keep)]
            if device:
                A = jnp.take(A, jnp.asarray(np.where(keep)[0]), axis=0)
            else:
                A = A[keep]

        warm = None
        if alpha is not None and len(alpha) == len(bvec) - 1:
            warm = np.append(alpha * (1.0 - 1e-3), 1e-3)
        alpha, dual_val = solve_bundle_dual(G, bvec, lam, alpha0=warm)

        w_t = -(A.T @ (jnp.asarray(alpha, jnp.float32) if device
                       else alpha)) / (2.0 * lam)
        wt_sq = float(w_t @ w_t)
        # J_t(w_t) = max_i (a_i . w_t + b_i) + lam ||w_t||^2, all via G.
        aw = -(G @ alpha) / (2.0 * lam)
        jt = float(np.max(aw + bvec) + lam * wt_sq)

        gap = j_best - jt
        stats.loss_history.append(r_emp)
        stats.gap_history.append(gap)
        stats.iterations = t
        if callback is not None:
            callback(t, w_t, j_best, gap)

        if gap < eps:
            stats.converged = True
            w_prev = w_t
            break
        w_prev = w_t

    stats.obj_best = float(j_best)
    stats.gap = float(stats.gap_history[-1]) if stats.gap_history else np.inf
    return BMRMResult(w=np.asarray(w_best, np.float64), stats=stats)


# ----------------------------------------------------------- device driver


class BundleState(NamedTuple):
    """Fixed-capacity cutting-plane state, entirely device-resident.

    K = max_planes is the static buffer capacity; `n_active` counts the
    planes actually inserted so far (slots [0, n_active) — inserts fill
    sequentially, and past capacity the smallest-alpha slot is overwritten
    in place, so the active set is always a prefix).

    `S` records, per plane slot, the support iterate the plane was cut at
    (plane i is the tangent of R_emp at S[i]). The solver itself never
    reads it back — it exists for the data-warm-start contract
    (`core.incremental`, DESIGN.md §11): knowing each plane's tangent
    point lets a refit revalidate the plane for appended rows by
    evaluating the NEW rows' loss at S[i] only, O(planes·Δ) instead of
    O(planes·m).
    """

    w: jnp.ndarray         # (n,)   current iterate w_t
    w_best: jnp.ndarray    # (n,)   best-J iterate (Franc & Sonnenburg)
    j_best: jnp.ndarray    # ()     J(w_best)
    A: jnp.ndarray         # (K, n) plane gradients a_i
    b: jnp.ndarray         # (K,)   plane offsets b_i
    G: jnp.ndarray         # (K, K) Gram A A^T (active block)
    alpha: jnp.ndarray     # (K,)   bundle dual (zero outside active set)
    n_active: jnp.ndarray  # ()     int32 planes in buffer
    gap: jnp.ndarray       # ()     J(w_best) - D(alpha)
    done: jnp.ndarray      # ()     bool, gap < eps reached
    S: jnp.ndarray         # (K, n) support iterate each plane was cut at


def init_bundle_state(dim: int, max_planes: int,
                      w0=None) -> BundleState:
    w = (jnp.zeros(dim, f32) if w0 is None
         else jnp.asarray(np.asarray(w0), f32))
    K = int(max_planes)
    return BundleState(
        w=w, w_best=w, j_best=jnp.asarray(np.inf, f32),
        A=jnp.zeros((K, dim), f32), b=jnp.zeros((K,), f32),
        G=jnp.zeros((K, K), f32), alpha=jnp.zeros((K,), f32),
        n_active=jnp.asarray(0, jnp.int32),
        gap=jnp.asarray(np.inf, f32), done=jnp.asarray(False),
        S=jnp.zeros((K, dim), f32))


def bundle_state_from_planes(A, b, S, dim: int, max_planes: int,
                             w0=None, alpha=None) -> BundleState:
    """Rebuild a warm-startable `BundleState` from bare planes.

    The inverse of "read (A, b, S) off a fitted state": `core.incremental`
    revalidates retained planes for changed data on the host and re-enters
    the device driver through here. The P <= max_planes planes land in
    slots [0, P); the Gram block is recomputed (A is f32 already, so
    A A^T matches what incremental insertion would have produced), and
    `alpha` (default uniform over the P planes) seeds the first masked QP.
    Scalar statistics start reset exactly like a lambda warm start: the
    first bundle_step cuts a fresh tangent at w0 and the QP immediately
    optimizes over old + new planes together.
    """
    A = np.asarray(A, np.float32)
    b = np.asarray(b, np.float32).ravel()
    S = np.asarray(S, np.float32)
    K = int(max_planes)
    P = len(b)
    if A.shape != (P, int(dim)) or S.shape != (P, int(dim)):
        raise ValueError(f'planes A{A.shape}/S{S.shape} do not match '
                         f'({P}, {int(dim)})')
    if P > K:
        raise ValueError(f'{P} planes exceed the max_planes={K} buffer; '
                         'trim to the highest-dual-weight planes first')
    st = init_bundle_state(dim, K, w0)
    if P == 0:
        return st
    if alpha is None:
        al = np.full(P, 1.0 / P, np.float32)
    else:
        al = np.asarray(alpha, np.float32).ravel()
        if al.shape != (P,):
            raise ValueError(f'alpha has shape {al.shape}, expected ({P},)')
        s = float(al.sum())
        al = al / s if s > 0 else np.full(P, 1.0 / P, np.float32)
    A_buf = np.zeros((K, int(dim)), np.float32)
    A_buf[:P] = A
    S_buf = np.zeros((K, int(dim)), np.float32)
    S_buf[:P] = S
    b_buf = np.zeros(K, np.float32)
    b_buf[:P] = b
    al_buf = np.zeros(K, np.float32)
    al_buf[:P] = al
    G = np.zeros((K, K), np.float32)
    G[:P, :P] = A @ A.T
    return st._replace(
        A=jnp.asarray(A_buf), b=jnp.asarray(b_buf), S=jnp.asarray(S_buf),
        G=jnp.asarray(G), alpha=jnp.asarray(al_buf),
        n_active=jnp.asarray(P, jnp.int32))


def bundle_state_shardings(mesh, batched: bool = False) -> BundleState:
    """Sharding annotations for a `BundleState` living on `mesh` (the
    sharded-oracle pod path, DESIGN.md §5).

    The plane buffer A is the only O(K n) object: it is column-sharded over
    'model' exactly like the subgradients the oracle emits, so plane insert
    (`dynamic_update_slice`) and the master-problem matvec `A.T @ alpha`
    run shard-local with no per-step resharding. Everything O(K) or O(K^2)
    — offsets, Gram, dual, scalars — plus the iterates w / w_best is
    replicated: the QP is K-sized host-scale math that every device
    redundantly computes faster than it could communicate about it.

    With `batched=True` the annotations describe the (n_lams, ...)-leading
    state of the batched path sweep (`bmrm_path(mode='vmap')`, DESIGN.md
    §7): the lambda axis is replicated (each device carries every lambda's
    slice of its feature shard), so only the plane buffer's spec changes —
    P(None, None, 'model') — and `PartitionSpec()` annotations stay valid
    for the extra leading axis as-is.
    """
    rep = NamedSharding(mesh, P())
    a_spec = P(None, None, 'model') if batched else P(None, 'model')
    kn = NamedSharding(mesh, a_spec)     # the two O(K n) buffers: A and S
    return BundleState(
        w=rep, w_best=rep, j_best=rep,
        A=kn, b=rep, G=rep, alpha=rep,
        n_active=rep, gap=rep, done=rep, S=kn)


def abstract_bundle_state(dim: int, max_planes: int) -> BundleState:
    """ShapeDtypeStruct stand-ins for one BundleState (compile-only
    dry-runs of the full sharded bundle_step; launch.dryrun)."""
    K = int(max_planes)
    s = jax.ShapeDtypeStruct
    return BundleState(
        w=s((dim,), f32), w_best=s((dim,), f32), j_best=s((), f32),
        A=s((K, dim), f32), b=s((K,), f32), G=s((K, K), f32),
        alpha=s((K,), f32), n_active=s((), jnp.int32),
        gap=s((), f32), done=s((), jnp.bool_), S=s((K, dim), f32))


def _bundle_step(s: BundleState, step_fn, lam, eps, qp_iters: int):
    """ONE fully-traced BMRM iteration over the fixed-capacity state.

    After the oracle step, its work runs under two named scopes: the
    best-iterate bookkeeping and plane insert under 'plane_insert', the
    QP, the iterate and the gap under 'qp'."""
    K = s.b.shape[0]
    r_emp, a = step_fn(s.w)
    r_emp = r_emp.astype(f32)
    a = a.astype(f32)

    with jax.named_scope('plane_insert'):
        wa = s.w @ a
        j_prev = r_emp + lam * (s.w @ s.w)
        better = j_prev < s.j_best
        j_best = jnp.where(better, j_prev, s.j_best)
        w_best = jnp.where(better, s.w, s.w_best)

        # Insert slot: next free, or (buffer full) the least-active plane.
        idx = jnp.arange(K, dtype=jnp.int32)
        full = s.n_active >= K
        masked_alpha = jnp.where(idx < s.n_active, s.alpha, jnp.inf)
        slot = jnp.where(full, jnp.argmin(masked_alpha).astype(jnp.int32),
                         s.n_active)
        A = jax.lax.dynamic_update_slice(s.A, a[None, :], (slot, 0))
        # The slot's support iterate: the plane just inserted is R_emp's
        # tangent at s.w — recorded so data warm starts (core.incremental)
        # can revalidate the plane for appended rows at exactly this point.
        S = jax.lax.dynamic_update_slice(s.S, s.w[None, :], (slot, 0))
        cross = A @ a                    # rows >= n_active are zero-filled
        G = s.G.at[slot, :].set(cross).at[:, slot].set(cross)
        b = s.b.at[slot].set(r_emp - wa)
        n_active = jnp.minimum(s.n_active + 1, K)
        mask = idx < n_active

    with jax.named_scope('qp'):
        # Warm-started masked QP; the new plane enters with a small weight
        # and the projection inside the solver renormalizes onto the
        # simplex.
        alpha0 = s.alpha.at[slot].set(1e-3)
        alpha, dual = solve_bundle_dual_jax(G, b, lam, mask, alpha0=alpha0,
                                            n_iter=qp_iters)
        w = -(A.T @ alpha) / (2.0 * lam)

        # Gap against the DUAL value: D(alpha) <= min_w J_t(w) for any
        # feasible alpha, so an under-converged QP inflates the gap instead
        # of faking convergence.
        gap = j_best - dual
        done = s.done | (gap < eps)
    return BundleState(w=w, w_best=w_best, j_best=j_best, A=A, b=b, G=G,
                       alpha=alpha, n_active=n_active, gap=gap,
                       done=done, S=S), r_emp


# Compiled chunk caches. `_CHUNK_CACHE` is per-oracle (the traced step_fn
# closes over its arrays), keyed by the static config; lam/eps are traced
# arguments, so one compilation serves a whole regularization-path sweep.
# `_SHARED_CHUNKS` is the cross-instance cache for oracles exposing the
# `step_parts` split (the fused single-device oracles): the data pytree is
# a traced ARGUMENT there, so a fresh oracle over fresh data — every
# incremental refit builds one — reuses the compiled chunk of any earlier
# same-signature oracle instead of paying seconds of retrace/recompile
# per call (jit still re-traces on genuinely new data shapes).
_CHUNK_CACHE: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
_SHARED_CHUNKS: dict = {}


def _shared_chunk(oracle, key, build):
    """Cross-instance chunk lookup: returns a `(state, *scalars)` callable
    with the oracle's data pytree bound, or None when the oracle cannot
    share (no `step_parts`, or a mesh oracle whose state shardings are
    pinned per instance)."""
    parts = getattr(oracle, 'step_parts', None)
    if not callable(parts) or _oracle_state_shardings(oracle) is not None:
        return None
    fn, data = parts()
    key = (oracle.step_signature(),) + key
    jitted = _SHARED_CHUNKS.get(key)
    if jitted is None:
        jitted = _SHARED_CHUNKS[key] = jax.jit(build(fn))
    return lambda state, *scalars: jitted(state, *scalars, data)


def _scan_chunk(step_fn, lam, eps, qp_iters, sync_every, state):
    """`sync_every` fused bundle steps as one lax.scan (skipping once
    done) — the traced body both chunk caches jit."""
    def body(s, _):
        def run(s):
            s2, r = _bundle_step(s, step_fn, lam, eps, qp_iters)
            return s2, (r, s2.gap, jnp.asarray(True))

        def skip(s):
            return s, (jnp.asarray(np.nan, f32), s.gap,
                       jnp.asarray(False))

        return jax.lax.cond(s.done, skip, run, s)

    return jax.lax.scan(body, state, None, length=sync_every)


def _device_chunk(oracle, max_planes: int, sync_every: int, qp_iters: int):
    def build(fn):
        def chunk(state: BundleState, lam, eps, data):
            return _scan_chunk(lambda w: fn(w, data), lam, eps, qp_iters,
                               sync_every, state)

        return chunk

    shared = _shared_chunk(oracle, (max_planes, sync_every, qp_iters),
                           build)
    if shared is not None:
        return shared

    try:
        per = _CHUNK_CACHE.setdefault(oracle, {})
    except TypeError:              # non-weakrefable oracle: build uncached
        per = {}
    key = (max_planes, sync_every, qp_iters)
    if key not in per:
        step_fn = oracle.step_fn()

        def chunk(state: BundleState, lam, eps):
            return _scan_chunk(step_fn, lam, eps, qp_iters, sync_every,
                               state)

        sh = _oracle_state_shardings(oracle)
        if sh is None:
            per[key] = jax.jit(chunk)
        else:
            # Mesh oracle: pin the bundle state's shardings on BOTH sides
            # of the chunk so state threads through the whole sweep without
            # per-chunk resharding (the plane buffer stays column-sharded).
            rep = NamedSharding(sh.A.mesh, P())
            per[key] = jax.jit(chunk, in_shardings=(sh, rep, rep),
                               out_shardings=(sh, (rep, rep, rep)))
    return per[key]


def _oracle_state_shardings(oracle, batched: bool = False):
    """BundleState shardings for mesh oracles (None for single-device).

    `batched=True` asks for the (n_lams, ...)-leading annotations of the
    vmapped path sweep (see `bundle_state_shardings`)."""
    fn = getattr(oracle, 'state_shardings', None)
    if not callable(fn):
        return None
    return fn(batched=True) if batched else fn()


def _next_sync_every(gaps: np.ndarray, eps: float, cur: int) -> int:
    """Pick the next chunk length from the observed gap decay.

    Fits a geometric decay rate to the last chunk's gap trajectory,
    predicts the remaining steps to eps, and sizes the next chunk at about
    half that — so the convergence overshoot (up to chunk-1 wasted fused
    steps) stays bounded by the remaining useful work. Chunk lengths are
    powers of two in [1, AUTO_SYNC_MAX] to bound jit-cache growth.
    """
    gaps = np.asarray([g for g in gaps if np.isfinite(g) and g > 0.0])
    if len(gaps) and gaps[-1] <= eps:
        return max(1, min(cur, AUTO_SYNC_MAX))   # about to converge
    if len(gaps) < 2:
        # No decay signal (also the only escape from cur == 1, whose
        # chunks yield a single gap sample): grow to amortize dispatch.
        return max(1, min(2 * cur, AUTO_SYNC_MAX))
    rate = (gaps[-1] / gaps[0]) ** (1.0 / (len(gaps) - 1))
    if not (0.0 < rate < 1.0):         # gap not (yet) decaying: no signal,
        return min(2 * cur, AUTO_SYNC_MAX)   # amortize dispatch harder
    n_rem = math.log(gaps[-1] / eps) / math.log(1.0 / rate)
    target = max(1.0, n_rem / 2.0)
    return int(min(1 << int(math.floor(math.log2(target))), AUTO_SYNC_MAX))


def _bmrm_device(oracle, dim, lam, eps, max_iter, w0, max_planes, callback,
                 sync_every, qp_iters, state) -> BMRMResult:
    """Device driver: `sync_every` fused bundle_steps per host round-trip."""
    K = int(max_planes) if max_planes is not None else DEFAULT_MAX_PLANES
    auto_sync = sync_every == 'auto'
    cur_sync = AUTO_SYNC_INIT if auto_sync else max(1, int(sync_every))

    if state is None:
        state = init_bundle_state(dim, K, w0)
    else:
        if state.A.shape != (K, dim):
            raise ValueError(f'warm-start state has buffer '
                             f'{tuple(state.A.shape)}, expected {(K, dim)}')
        # Planes stay (they under-estimate R_emp for ANY lam); the scalar
        # statistics are lam-dependent and reset.
        state = state._replace(
            w=state.w if w0 is None else jnp.asarray(np.asarray(w0), f32),
            w_best=state.w, j_best=jnp.asarray(np.inf, f32),
            gap=jnp.asarray(np.inf, f32), done=jnp.asarray(False))
    sh = _oracle_state_shardings(oracle)
    if sh is not None:
        # Mesh oracle: commit the state to its annotated shardings up front
        # (replicated scalars/QP state, column-sharded plane buffer) so the
        # first chunk already runs without resharding.
        state = jax.device_put(state, sh)

    lam_d = jnp.asarray(lam, f32)
    eps_d = jnp.asarray(eps, f32)
    stats = BMRMStats(0, False, np.inf, np.inf, [], [], [],
                      solver='device')

    # Fit-local chunk cache: bounds compiles to the distinct chunk lengths
    # even for non-weakrefable oracles (where _CHUNK_CACHE can't help).
    chunks: dict = {}
    # Host spans, for traces: 'bmrm.dispatch' around the chunk call,
    # 'bmrm.sync' around every device-to-host read of the chunk (and, after
    # the last chunk, of the fit's end).
    while True:                       # always >= 1 chunk (matches ceil())
        chunk = chunks.get(cur_sync)
        if chunk is None:
            chunk = _device_chunk(oracle, K, cur_sync, qp_iters)
            chunks[cur_sync] = chunk
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation('bmrm.dispatch'):
            state, (losses, gaps, valids) = chunk(state, lam_d, eps_d)
        with jax.profiler.TraceAnnotation('bmrm.sync'):
            v = np.asarray(valids)           # the one sync point per chunk
            dt = time.perf_counter() - t0
            steps = int(v.sum())
            losses = np.asarray(losses, np.float64)[v]
            gaps = np.asarray(gaps, np.float64)[v]
            done = bool(state.done)
            last = done or stats.iterations + steps >= max_iter
            if callback is not None or last:
                j_best, gap = float(state.j_best), float(state.gap)
            if last:
                w_best = np.asarray(state.w_best, np.float64)
        if steps:
            stats.loss_history.extend(losses)
            stats.gap_history.extend(gaps)
            stats.oracle_seconds.extend([dt / steps] * steps)
            stats.iterations += steps
        if callback is not None:
            callback(stats.iterations, state.w, j_best, gap)
        if last:
            break
        if auto_sync:
            cur_sync = _next_sync_every(gaps, eps, cur_sync)

    stats.converged = done
    stats.obj_best = j_best
    stats.gap = gap
    return BMRMResult(w=w_best, stats=stats, state=state)


# ------------------------------------------------------ batched path sweep


PATH_MODES = ('vmap', 'sequential', 'hybrid', 'auto')

# Default sequential-warm prefix of mode='hybrid': two fits are enough to
# fill the bundle with tight planes of the risk surface (the first fit
# does the heavy lifting; the second starts warm and converges in a few
# steps) while keeping the forfeited parallel width minimal.
DEFAULT_HYBRID_PREFIX = 2


def _validate_path_mode(mode: str) -> str:
    """The one mode check both `bmrm_path` and `RankSVM.path` run —
    the estimator calls it BEFORE building its (possibly expensive)
    oracle, so a typo'd mode fails in microseconds, not after a sharded
    bf16 densify/transfer."""
    if mode not in PATH_MODES:
        raise ValueError(f'unknown path mode {mode!r}; expected one of '
                         f'{PATH_MODES}')
    return mode


def _validate_lams(lams) -> list:
    """Regularization-path lambdas as a validated list of floats.

    Any order (including unsorted or duplicated values) is accepted — the
    vmap driver treats lambdas independently, and the sequential driver's
    warm-started planes are valid cuts for ANY lambda — but every value
    must be a finite positive float: lambda divides the master-problem
    update w = -A'alpha / (2 lam), so 0/inf/NaN would silently poison the
    whole sweep.
    """
    try:
        lams = [float(lam) for lam in np.asarray(lams).ravel()]
    except (TypeError, ValueError) as e:
        raise ValueError(f'path lambdas must be real numbers; got {lams!r}'
                         ) from e
    if not lams:
        raise ValueError('a regularization path needs at least one lambda')
    tiny = float(np.finfo(np.float32).tiny)      # smallest NORMAL f32
    bad = [lam for lam in lams if not math.isfinite(lam) or lam <= 0.0
           or not tiny <= float(np.float32(lam)) < math.inf]
    if bad:
        raise ValueError(
            f'path lambdas must be finite, > 0, and a normal float32 (in '
            f'[{tiny:.3g}, ~3.4e38]) — the device drivers compute in f32 '
            f'— got {bad}: lambda scales 1/(2 lam) in the master problem, '
            'so a value that is zero/non-finite, overflows the f32 cast, '
            'or lands subnormal (reciprocal overflows; TPUs flush '
            'subnormals to zero) poisons every iterate')
    return lams


def path_state_gib(n_lams: int, dim: int, max_planes: int | None = None,
                   m: int = 0) -> float:
    """Projected resident GiB of the batched (vmap) path sweep.

    The memory model behind `bmrm_path(mode='auto')`'s vmap-vs-sequential
    guard (the batched analogue of `data.rowblocks.projected_resident_gib`):
    each of the K = `n_lams` lambdas carries its own f32 `BundleState` —
    the (max_planes, dim) plane buffer dominates — plus roughly the fused
    oracle step's O(m) per-example working set (score vector, count
    coefficients and their sort temporaries, ~8 f32 values per example).
    Shared, lambda-independent residency (the feature matrix itself) is
    NOT included: it is identical across path modes. Estimates assume the
    single-device layout; on a mesh the plane buffer is column-sharded so
    the per-device number is smaller.
    """
    planes = int(max_planes) if max_planes is not None else DEFAULT_MAX_PLANES
    per_lam = 4.0 * (2 * planes * dim     # plane buffer A + iterate buffer S
                     + 2 * dim            # w, w_best
                     + planes * planes    # Gram
                     + 3 * planes + 8     # b, alpha, masks, scalars
                     + 8 * m)             # oracle-step per-example work set
    return int(n_lams) * per_lam / 2**30


def init_path_state(dim: int, max_planes: int, n_lams: int,
                    w0=None, state: 'BundleState | None' = None
                    ) -> BundleState:
    """A (n_lams, ...)-leading `BundleState`: slice k along the first axis
    of every leaf is lambda k's independent bundle state.

    Without `state` every lambda starts cold from the shared w0. With
    `state` (a scalar `BundleState`, e.g. the final state of a
    sequential-warm prefix — the two-phase hybrid sweep) every lambda's
    slice starts from THAT state's plane buffer instead: planes
    under-estimate R_emp independently of lambda, so they are valid
    cuts for every lambda in the batch, and only the lam-dependent
    scalar statistics reset (same rule as `bmrm(..., state=)`)."""
    if state is None:
        s = init_bundle_state(dim, max_planes, w0)
    else:
        if tuple(state.A.shape) != (int(max_planes), int(dim)):
            raise ValueError(f'seed state has buffer '
                             f'{tuple(state.A.shape)}, expected '
                             f'{(int(max_planes), int(dim))}')
        s = state._replace(
            w=state.w if w0 is None else jnp.asarray(np.asarray(w0), f32),
            w_best=state.w, j_best=jnp.asarray(np.inf, f32),
            gap=jnp.asarray(np.inf, f32), done=jnp.asarray(False))
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (int(n_lams),) + x.shape), s)


def _bundle_step_masked(s: BundleState, step_fn, lam, eps, qp_iters: int):
    """One per-lambda bundle step with the done-mask freeze: a converged
    lambda's state passes through unchanged (no new plane, no QP result,
    no statistics drift), so under vmap it is a no-op — never a barrier
    for the still-running lambdas. Returns (state, loss-or-NaN, active)."""
    s2, r = _bundle_step(s, step_fn, lam, eps, qp_iters)
    frozen = jax.tree_util.tree_map(
        lambda new, old: jnp.where(s.done, old, new), s2, s)
    return (frozen, jnp.where(s.done, jnp.asarray(np.nan, f32), r),
            jnp.logical_not(s.done))


def _path_scan_chunk(step_fn, lams, eps, n_lams, qp_iters, sync_every,
                     state):
    """`sync_every` vmapped bundle steps as one lax.scan — the batched
    analogue of `_scan_chunk`, carrying the (n_lams, ...) state."""
    def body(s, _):
        def run(s):
            s2, r, act = jax.vmap(
                lambda sk, lamk: _bundle_step_masked(
                    sk, step_fn, lamk, eps, qp_iters))(s, lams)
            return s2, (r, s2.gap, act)

        def skip(s):
            return s, (jnp.full((n_lams,), np.nan, f32), s.gap,
                       jnp.zeros((n_lams,), bool))

        # Scalar predicate (ALL lambdas done) -> a real cond: the
        # per-lambda freeze happens inside the vmapped step.
        return jax.lax.cond(jnp.all(s.done), skip, run, s)

    return jax.lax.scan(body, state, None, length=sync_every)


def _path_chunk(oracle, n_lams: int, max_planes: int, sync_every: int,
                qp_iters: int):
    """Compiled `sync_every`-step chunk of the BATCHED path sweep: the
    vmapped analogue of `_device_chunk`, carrying the (n_lams, ...) state.
    Shared across same-signature oracles when possible, else cached per
    oracle alongside the scalar chunks (disjoint keys)."""
    def build(fn):
        def chunk(state: BundleState, lams, eps, data):
            return _path_scan_chunk(lambda w: fn(w, data), lams, eps,
                                    n_lams, qp_iters, sync_every, state)

        return chunk

    shared = _shared_chunk(oracle, ('path', n_lams, max_planes,
                                    sync_every, qp_iters), build)
    if shared is not None:
        return shared

    try:
        per = _CHUNK_CACHE.setdefault(oracle, {})
    except TypeError:              # non-weakrefable oracle: build uncached
        per = {}
    key = ('path', n_lams, max_planes, sync_every, qp_iters)
    if key not in per:
        step_fn = oracle.step_fn()

        def chunk(state: BundleState, lams, eps):
            return _path_scan_chunk(step_fn, lams, eps, n_lams, qp_iters,
                                    sync_every, state)

        sh = _oracle_state_shardings(oracle, batched=True)
        if sh is None:
            per[key] = jax.jit(chunk)
        else:
            rep = NamedSharding(sh.A.mesh, P())
            per[key] = jax.jit(chunk, in_shardings=(sh, rep, rep),
                               out_shardings=(sh, (rep, rep, rep)))
    return per[key]


def _bmrm_path_vmap(oracle, lams, dim, eps, max_iter, w0, max_planes,
                    sync_every, qp_iters, callback,
                    init_state: 'BundleState | None' = None
                    ) -> 'list[BMRMResult]':
    """The batched path driver: ONE device program sweeps every lambda.

    The (K, ...)-leading `BundleState` runs through the same chunked
    `lax.scan` as `_bmrm_device`, with `_bundle_step` and the masked FISTA
    QP vmapped over the lambda axis. Per-lambda done flags freeze converged
    slices; the host loop exits when all K are done (or the shared step
    count hits max_iter — lambdas advance in lockstep, so the cap is per
    lambda and global at once).
    """
    K = int(max_planes) if max_planes is not None else DEFAULT_MAX_PLANES
    n_lams = len(lams)
    auto_sync = sync_every == 'auto'
    cur_sync = AUTO_SYNC_INIT if auto_sync else max(1, int(sync_every))

    state = init_path_state(dim, K, n_lams, w0, state=init_state)
    sh = _oracle_state_shardings(oracle, batched=True)
    if sh is not None:
        state = jax.device_put(state, sh)
    lams_d = jnp.asarray(lams, f32)
    eps_d = jnp.asarray(eps, f32)

    iters = np.zeros(n_lams, np.int64)
    loss_hist = [[] for _ in range(n_lams)]
    gap_hist = [[] for _ in range(n_lams)]
    secs = [[] for _ in range(n_lams)]
    steps_total = 0
    chunks: dict = {}
    while True:
        chunk = chunks.get(cur_sync)
        if chunk is None:
            chunk = _path_chunk(oracle, n_lams, K, cur_sync, qp_iters)
            chunks[cur_sync] = chunk
        t0 = time.perf_counter()
        state, (losses, gaps, acts) = chunk(state, lams_d, eps_d)
        acts = np.asarray(acts)                     # (sync, K) — the sync
        dt = time.perf_counter() - t0
        losses = np.asarray(losses, np.float64)
        gaps_np = np.asarray(gaps, np.float64)
        ran = acts.any(axis=1)                      # batched steps that ran
        steps = int(ran.sum())
        steps_total += steps
        # Per-lambda time attribution: each batched step's wall is split
        # evenly over the lambdas ACTIVE in it, so per-lambda seconds sum
        # to ~the program's wall across the sweep (stats.seconds below is
        # exactly sum(oracle_seconds), keeping FitReport arithmetic
        # consistent: seconds == iterations * oracle_seconds_mean).
        n_active = acts.sum(axis=1)
        step_wall = dt / max(steps, 1)
        for k in range(n_lams):
            on = acts[:, k]
            nk = int(on.sum())
            if nk:
                iters[k] += nk
                loss_hist[k].extend(losses[on, k])
                gap_hist[k].extend(gaps_np[on, k])
                secs[k].extend(step_wall / n_active[on])
        if callback is not None:
            callback(steps_total, state.w, np.asarray(state.j_best),
                     np.asarray(state.gap))
        if bool(np.all(np.asarray(state.done))) or steps_total >= max_iter:
            break
        if auto_sync:
            # Tune on the slowest lambda: ALL-done is the exit condition,
            # so the max active gap governs the remaining work.
            act_gaps = np.where(acts[ran], gaps_np[ran], -np.inf)
            cur_sync = _next_sync_every(act_gaps.max(axis=1), eps, cur_sync)

    done = np.asarray(state.done)
    j_best = np.asarray(state.j_best, np.float64)
    gap = np.asarray(state.gap, np.float64)
    w_best = np.asarray(state.w_best, np.float64)
    results = []
    for k in range(n_lams):
        stats = BMRMStats(
            iterations=int(iters[k]), converged=bool(done[k]),
            obj_best=float(j_best[k]), gap=float(gap[k]),
            loss_history=loss_hist[k], gap_history=gap_hist[k],
            oracle_seconds=secs[k], solver='vmap',
            seconds=float(np.sum(secs[k])))
        state_k = jax.tree_util.tree_map(lambda x, k=k: x[k], state)
        results.append(BMRMResult(w=w_best[k], stats=stats, state=state_k))
    return results


def bmrm_path(oracle, lams, *, mode: str = 'auto', eps: float = 1e-3,
              max_iter: int = 1000, w0: np.ndarray | None = None,
              max_planes: int | None = None, solver: str = 'auto',
              sync_every: 'int | str' = 8, qp_iters: int = 128,
              memory_budget: float | None = None,
              hybrid_prefix: int = DEFAULT_HYBRID_PREFIX,
              callback: Callable | None = None) -> 'list[BMRMResult]':
    """Sweep a regularization path over `lams`; one BMRMResult per lambda.

    Args:
      oracle: a RankOracle (`core.oracle.make_oracle`). Bare callables are
        not accepted here — use `bmrm` per lambda.
      lams: lambda values, any order; each must be finite and > 0
        (`_validate_lams`). Duplicates are allowed.
      mode: 'vmap' | 'sequential' | 'hybrid' | 'auto' —
        * 'vmap': ONE batched device program trains all K lambdas
          simultaneously over a (K, ...)-leading `BundleState` (DESIGN.md
          §7). Requires an oracle whose traced step batches
          (`supports_path_vmap`: the fused and sharded oracles; the
          streaming oracle's pure_callback fetches do not vmap).
        * 'sequential': one fit per lambda in order, warm-starting each
          from the previous (bundle state on the device driver, w0 on the
          host driver).
        * 'hybrid': two phases — sequential-warm the first
          `hybrid_prefix` lambdas, then broadcast the LAST prefix fit's
          plane buffer as every remaining lambda's initial state
          (`init_path_state(state=)`) and batch the rest as one vmap
          program. Recovers (part of) the warm-start iteration saving
          the pure batched sweep forfeits while keeping its parallel
          width for the grid's tail; requirements are vmap's (batchable
          oracle, device solver). Results come back in `lams` order.
        * 'auto' (default): vmap when the oracle supports it, the
          configured `solver` allows the device driver, eps is at or above
          the f32 floor, the backend is not the serial CPU (where the
          batched sweep measures 2-8x slower than sequential-warm,
          EXPERIMENTS §Path sweep), AND the projected batched state fits
          `memory_budget` (`path_state_gib`); else sequential. The
          memory fallback warns loudly.
      eps: termination gap per lambda, as in `bmrm` (f32 floor included).
      max_iter: as in `bmrm`; in vmap mode lambdas advance in lockstep,
        so this caps each lambda's (equal) step count.
      w0: optional shared warm-start iterate, as in `bmrm` (vmap mode:
        every lambda's slice starts from it).
      max_planes: per-lambda bundle capacity, as in `bmrm`; the vmap
        state scales as n_lams * max_planes * n floats.
      solver: as in `bmrm` for the sequential fits; for mode resolution
        'host' forces sequential (the batched driver is device-only).
      sync_every: fused steps per host sync, as in `bmrm` ('auto' tunes
        on the slowest active lambda's gap decay in vmap mode).
      qp_iters: fixed FISTA iterations of the on-device QP, as in `bmrm`.
      memory_budget: GiB the batched sweep may add in per-lambda state
        (same unit as `RankSVM(memory_budget=)`). Exceeding it falls back
        to sequential with a RuntimeWarning — even under mode='vmap', on
        the grounds that an explicit budget outranks an explicit mode
        (pass memory_budget=None to force vmap regardless). For
        mode='hybrid' the projection covers only the batched phase's
        `len(lams) - hybrid_prefix` lambdas.
      hybrid_prefix: mode='hybrid' only — how many leading lambdas run
        sequentially warm before the batched phase (default
        DEFAULT_HYBRID_PREFIX = 2). A prefix >= len(lams) degenerates to
        the pure sequential sweep.
      callback: per-sync callback. Sequential: forwarded to each `bmrm`
        call unchanged. vmap: called as callback(total_steps, W, J, G)
        with (K, ...)-shaped batched values. Hybrid: each phase's
        convention in turn.
    """
    _validate_path_mode(mode)
    if solver not in SOLVERS:
        # Validate up front: the vmap branch never reaches bmrm()'s own
        # check, and a typo'd solver must not silently resolve to vmap.
        raise ValueError(f'unknown solver {solver!r}; expected one of '
                         f'{SOLVERS}')
    if not hasattr(oracle, 'loss_and_subgrad'):
        raise ValueError('bmrm_path needs a RankOracle (make_oracle); for '
                         'bare callables run bmrm once per lambda')
    lams = _validate_lams(lams)
    dim = int(oracle.n)
    batchable = bool(getattr(oracle, 'supports_path_vmap', False))

    if mode in ('vmap', 'hybrid'):
        if not batchable:
            raise ValueError(
                f"mode={mode!r} needs an oracle whose traced step batches "
                f'over lambda (supports_path_vmap); {type(oracle).__name__}'
                ' does not — the streaming oracle pulls host row blocks '
                'through pure_callback, which cannot vmap. Use '
                "mode='sequential' (or 'auto')")
        if solver == 'host':
            raise ValueError(f"mode={mode!r} runs a device-driver program;"
                             " it cannot run under solver='host' — pass "
                             "solver='auto'/'device' or mode='sequential'")
        if eps < F32_EPS_FLOOR:
            # Same semantics as an explicit solver='device' below the
            # floor: honor the explicit mode, but say why it may never
            # converge (mode='auto' falls back to sequential instead).
            warnings.warn(
                f'eps={eps:g} is below the f32 noise floor of the batched '
                'bundle state; per-lambda gaps may stall above it and the '
                'lockstep sweep would then spin to max_iter — use '
                f"mode='sequential' for eps < {F32_EPS_FLOOR:g}",
                RuntimeWarning, stacklevel=2)
    if mode == 'hybrid':
        if not (isinstance(hybrid_prefix, (int, np.integer))
                and not isinstance(hybrid_prefix, bool)
                and int(hybrid_prefix) >= 1):
            raise ValueError('hybrid_prefix must be a positive int; got '
                             f'{hybrid_prefix!r}')

    def _over_budget(n_batched: int) -> bool:
        if memory_budget is None:
            return False
        projected = path_state_gib(n_batched, dim, max_planes,
                                   m=int(getattr(oracle, 'm', 0)))
        if projected > float(memory_budget):
            warnings.warn(
                f'batched path sweep over {n_batched} lambdas projects '
                f'~{projected:.3g} GiB of per-lambda bundle state + oracle '
                f'working set (path_state_gib), over the '
                f'{float(memory_budget):g} GiB memory_budget — falling '
                'back to the sequential warm-started sweep. Raise the '
                'budget, lower max_planes, or split the lambda grid to '
                'batch it.', RuntimeWarning, stacklevel=3)
            return True
        return False

    def _sequential(seq_lams, state=None, w_prev=None):
        results = []
        for lam in seq_lams:
            t0 = time.perf_counter()
            res = bmrm(oracle, lam=lam, eps=eps, max_iter=max_iter,
                       w0=w_prev, max_planes=max_planes, callback=callback,
                       solver=solver, sync_every=sync_every,
                       qp_iters=qp_iters, state=state)
            res.stats.seconds = time.perf_counter() - t0
            state = res.state        # None on the host driver
            w_prev = res.w
            results.append(res)
        return results

    if mode == 'hybrid':
        prefix = min(int(hybrid_prefix), len(lams))
        head = _sequential(lams[:prefix], w_prev=w0)
        tail_lams = lams[prefix:]
        if not tail_lams:
            return head
        seed = head[-1].state
        if seed is None or _over_budget(len(tail_lams)):
            # seed is None when solver='auto' resolved the prefix fits to
            # the host driver (e.g. a CPU-CSR oracle): there is no plane
            # buffer to broadcast, so finish the sweep sequentially-warm
            # (same warm quality, no batched phase).
            if seed is None:
                warnings.warn(
                    "mode='hybrid': the sequential prefix ran on the host "
                    'driver (no bundle state to broadcast) — finishing '
                    'the sweep sequentially', RuntimeWarning, stacklevel=2)
            return head + _sequential(tail_lams, state=seed,
                                      w_prev=head[-1].w)
        return head + _bmrm_path_vmap(
            oracle, tail_lams, dim=dim, eps=eps, max_iter=max_iter,
            w0=None, max_planes=max_planes, sync_every=sync_every,
            qp_iters=qp_iters, callback=callback, init_state=seed)

    # Measured backend exception (EXPERIMENTS §Path sweep, the path-mode
    # analogue of the oracle layer's csr_rmatvec rule): on the serial CPU
    # backend the batched sweep loses 2-8x to sequential-warm — no
    # parallel width to exploit, warm starts forfeited — so 'auto' keeps
    # CPU on the sequential sweep; an explicit mode='vmap' still batches.
    cpu_backend = jax.default_backend() == 'cpu'
    use_vmap = mode == 'vmap' or (
        mode == 'auto' and batchable and solver != 'host'
        and getattr(oracle, 'prefer_device_solver', True)
        and eps >= F32_EPS_FLOOR and not cpu_backend)
    if use_vmap and _over_budget(len(lams)):
        use_vmap = False

    if use_vmap:
        return _bmrm_path_vmap(oracle, lams, dim=dim, eps=eps,
                               max_iter=max_iter, w0=w0,
                               max_planes=max_planes, sync_every=sync_every,
                               qp_iters=qp_iters, callback=callback)

    return _sequential(lams, w_prev=w0)
