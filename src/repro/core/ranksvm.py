"""RankSVM estimators: TreeRSVM (the paper's method) and PairRSVM (baseline).

`RankSVM` is a thin selector over the BMRM oracle layer (`core.oracle`):
`method=` picks the `RankOracle` implementation —

  'tree'    TreeRSVM: merge-sort-tree counts, O(ms + m log^2 m)/iteration
  'pairs'   PairRSVM: blocked O(m^2) pairwise counts (the paper's baseline)
  'auto'    counts_auto dispatch: Pallas pairwise kernel for small ranking
            problems on TPU, tree otherwise; with `memory_budget=` set (or
            an np.memmap / RowBlockSource X) it falls over to the
            streaming oracle when the projected fused residency exceeds
            the budget
  'sharded' pod-scale mesh oracle (core.distributed): dense input is 2-D
            sharded bf16, CSR input stays SPARSE (row-sharded padded-slot
            segment-sum matvecs at O(nnz) — no densification), and
            memmap/RowBlockSource input streams per host into the device
            shards (assemble_row_sharded, prefetched). Accepts `groups=`
            like every other method, and under solver='auto' trains on
            the device bundle driver with the bundle state sharded over
            the mesh (per-query LTR at pod scale)
  'stream'  out-of-core streaming oracle (core.oracle.StreamingOracle):
            two chunked passes over a row-block feature source
            (data.rowblocks — dense, CSR, or np.memmap-backed), peak
            memory O(block*n + m) regardless of m

— and hands it to `core.bmrm.bmrm`. Orthogonally, `solver=` picks the BMRM
driver (core.bmrm):

  'host'    float64 reference loop, one host round-trip set per iteration
  'device'  the whole iteration jitted on device (fused oracle step +
            plane-buffer insert + on-device bundle QP), scalars synced
            every `sync_every` steps — the low-overhead path at small and
            medium m, where host dispatch otherwise dominates
  'auto'    device whenever the oracle supports it, measures as
            profitable for its layout (CPU CSR oracles with a
            host-dispatched transpose-matvec stay on host), and eps is
            above the f32 noise floor (the default)

All count/subgradient work flows through the oracle's fused device-resident
step; this module touches no counting internals. Both 'tree' and 'pairs'
reach the same solution — the paper uses this parity as its Fig. 4 sanity
check, reproduced in benchmarks/fig4_test_error.py.

`RankSVM.path(X, y, lams, mode=)` sweeps a regularization path
(core.bmrm.bmrm_path): mode='vmap' batches ALL lambdas into one device
program over a (K, ...)-leading bundle state (DESIGN.md §7);
mode='sequential' fits one lambda at a time, reusing the device driver's
fixed-capacity bundle state across lambda values (cutting planes
under-estimate R_emp independently of lambda, so they remain valid cuts —
later fits start from an already-tight model of the risk); mode='auto'
(default) picks vmap for fused device-solver oracles on accelerator
backends within the memory budget, sequential otherwise (the serial CPU
backend stays sequential — measured 2-8x faster there, EXPERIMENTS
§Path sweep).

Feature matrices may be numpy arrays, repro.data.sparse.CSRMatrix, or
scipy.sparse (CSR recommended); the matvecs X @ w and X.T @ v are the O(ms)
terms of Theorem 2.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from . import rank_loss as _rank_loss
from ..data.rowblocks import BlockStore, projected_resident_gib
from ..data.rowblocks import _validate_block_rows as _validate_block
from ..data.rowblocks import _validate_prefetch
from .bmrm import (DEFAULT_MAX_PLANES, SOLVERS, _validate_lams,
                   _validate_path_mode, bmrm, bmrm_path)
from .counts import _validate_engine
from .incremental import (IncrementalFit, LEDGER_LOSSES, RefitReport,
                          block_partials)
from .oracle import METHODS, _validate_loss, empirical_risk, make_oracle

REFIT_MODES = ('ledger', 'w-only', 'auto')


def _matvec(X, w):
    if hasattr(X, 'matvec'):            # repro.data.sparse.CSRMatrix
        return X.matvec(w)
    return np.asarray(X @ w).ravel()


@dataclasses.dataclass
class FitReport:
    iterations: int
    converged: bool
    objective: float
    gap: float
    seconds: float
    oracle_seconds_mean: float
    loss_history: list
    solver: str = 'host'


@dataclasses.dataclass
class PathPoint:
    """One lambda of a regularization-path sweep (`RankSVM.path`)."""
    lam: float
    w: np.ndarray
    report: FitReport


class RankSVM:
    """Linear RankSVM trained with BMRM.

    Args:
      lam: regularization weight lambda of J(w) = R_emp(w) + lam ||w||^2
        (default 1e-3). SVM^rank-style C converts as C = 1 / (lam * N),
        see paper sec. 5.1. `path()` sweeps several lambdas in one call.
      eps: BMRM termination gap (default 1e-3, the paper's/SVM^rank's).
        The device driver keeps its bundle state in float32, whose
        duality gap carries an ~1e-6-relative noise floor: below
        eps = 1e-5 (`core.bmrm.F32_EPS_FLOOR`) solver='auto' falls back
        to the float64 host driver, and an explicit solver='device'
        warns that the gap may stall.
      method: oracle selector — 'tree' | 'pairs' | 'auto' | 'sharded' |
        'stream' (see module docstring; core.oracle.make_oracle holds the
        full dispatch table).
      loss: training objective — 'hinge' (default; the paper's uniform
        pairwise hinge over N preference pairs) | 'toppush' (each
        anchored example's margin against the MAX-scoring strictly-lower
        example in its group, normalized by the anchored count N+) |
        'poshinge' (pairwise hinge where pair (i, j) carries the
        higher-utility side's position-decay weight 1/log2(1+rank),
        normalized by the weight mass W) — DESIGN.md §12; validated at
        construction; every method composes except 'sharded', whose mesh
        bodies implement only the hinge and reject other losses up front
        (core.distributed.SHARDED_LOSSES). 'poshinge' additionally keeps
        no plane ledger (its position weights are not per-block
        decomposable — core.incremental.LEDGER_LOSSES), so `refit`
        warm-starts from w alone.
      engine: counting-engine override for the selected oracle
        (None | 'tree' | 'blocked' | 'pallas' | 'auto'), orthogonal to
        `method`'s memory model and validated at construction:

          engine     per-iteration counting pass
          None       the method's own default
          'tree'     merge-sort tree (one fused pass)
          'blocked'  O(m^2) pairwise, `pair_block`-row blocks
          'pallas'   fused rank-counts Pallas kernel — both frequency
                     vectors in one tiled on-chip pass (DESIGN.md §8)
          'auto'     measured tiering: Pallas pairwise then rank-counts
                     on TPU, tree lowering elsewhere (EXPERIMENTS.md
                     §Counts kernel)
      solver: BMRM driver — 'host' | 'device' | 'auto' (default 'auto';
        core.bmrm). 'auto' picks the fused device driver when the oracle
        supports and prefers it and eps is at or above the f32 floor.
      max_iter: BMRM iteration cap (default 1000). In `path(mode='vmap')`
        lambdas advance in lockstep, so the cap applies to each lambda's
        (equal) step count.
      max_planes: cutting-plane cap; for the device driver this is the
        static bundle-buffer capacity (default
        core.bmrm.DEFAULT_MAX_PLANES = 64). Also the per-lambda buffer
        capacity of the batched path sweep — its memory scales as
        n_lams * max_planes * n floats (core.bmrm.path_state_gib).
      sync_every: device driver: fused steps per host sync (default 8);
        'auto' retunes the chunk length from the observed gap-decay rate
        (core.bmrm).
      qp_iters: device driver: fixed FISTA iterations of the on-device
        bundle dual solve (default 128).
      pair_block: VMEM/cache block (rows) for the O(m^2) pairwise pass
        (default 2048).
      mesh: optional jax Mesh for method='sharded' (defaults to all local
        devices on the 'data' axis).
      memory_budget: GiB (float). Two dispatch decisions read it:
        method='auto' streams instead of fusing when the projected fused
        feature residency (`data.rowblocks.projected_resident_gib`)
        exceeds it, and `path(mode='auto'|'vmap')` falls back to the
        sequential sweep when the projected batched path state
        (`core.bmrm.path_state_gib`) exceeds it. None (default) disables
        both guards.
      stream_block: rows per block of the streaming oracle (default:
        budget-derived; core.oracle._auto_stream_block) and of the
        sharded oracle's per-host streamed assembly reads.
      prefetch: row-block read-ahead depth (None/'auto' | int >= 0) for
        the streaming oracle's chunked passes and the sharded oracle's
        per-host assembly: a background thread fetches up to `prefetch`
        blocks ahead of the consumer, hiding disk latency behind the
        matvec (`data.rowblocks._ReadAhead`). None/'auto' (default)
        double-buffers disk-backed memmap sources and stays synchronous
        for in-RAM dense/CSR layouts (`data.rowblocks.resolve_prefetch`);
        results are bit-identical at any depth. Validated up front;
        ignored by the fused oracles.
    """

    def __init__(self, lam: float = 1e-3, eps: float = 1e-3,
                 method: str = 'tree', max_iter: int = 1000,
                 pair_block: int = 2048, mesh=None, verbose: bool = False,
                 solver: str = 'auto', max_planes: int | None = None,
                 sync_every: 'int | str' = 8, qp_iters: int = 128,
                 memory_budget: float | None = None,
                 stream_block: int | None = None,
                 engine: str | None = None, prefetch=None,
                 loss: str = 'hinge'):
        if method not in METHODS:
            raise ValueError(f'unknown method {method!r}; '
                             f'expected one of {METHODS}')
        _validate_loss(loss)
        self.loss = loss
        if engine is not None:
            _validate_engine(engine)
        self.engine = engine
        if solver not in SOLVERS:
            raise ValueError(f'unknown solver {solver!r}; '
                             f'expected one of {SOLVERS}')
        self.lam = float(lam)
        self.eps = float(eps)
        self.method = method
        self.solver = solver
        self.max_iter = int(max_iter)
        self.max_planes = max_planes
        if isinstance(sync_every, str) and sync_every != 'auto':
            raise ValueError(f"unknown sync_every {sync_every!r}; expected "
                             "an int or 'auto'")
        self.sync_every = (sync_every if sync_every == 'auto'
                           else int(sync_every))
        self.qp_iters = int(qp_iters)
        self.pair_block = _validate_block(pair_block, 'pair_block')
        self.memory_budget = (None if memory_budget is None
                              else float(memory_budget))
        self.stream_block = (None if stream_block is None
                             else _validate_block(stream_block,
                                                  'stream_block'))
        _validate_prefetch(prefetch)    # fail at construction, not fit
        self.prefetch = prefetch
        self.mesh = mesh
        self.verbose = verbose
        self.w_: np.ndarray | None = None
        self.report_: FitReport | None = None
        self.oracle_ = None
        self.incremental_: IncrementalFit | None = None
        self.refit_report_: RefitReport | None = None

    # -- public API --------------------------------------------------------

    def fit(self, X, y=None, groups=None):
        """Learn w from features X (m, n) and real-valued utility scores y.

        X may also be a `data.rowblocks.BlockStore` (y/groups omitted —
        the store carries them); either way the fit leaves an
        `incremental_` handle behind, so `refit()` can later append or
        retire row blocks and warm-start from this solution instead of
        training cold (DESIGN.md §11).

        In a profiler trace the fit is the host span 'ranksvm.fit', holding
        'ranksvm.make_oracle' and 'ranksvm.solve'."""
        with TraceAnnotation('ranksvm.fit'):
            store, y, groups = self._as_store(X, y, groups)
            with TraceAnnotation('ranksvm.make_oracle'):
                oracle = self._make_oracle(
                    X if not isinstance(X, BlockStore) else store, y, groups)
            self.oracle_ = oracle

            t0 = time.perf_counter()
            with TraceAnnotation('ranksvm.solve'):
                res = self._solve(oracle, self.lam)
            dt = time.perf_counter() - t0

            self.w_ = res.w
            self.report_ = self._report(res, dt)
            self.incremental_ = IncrementalFit(store, res.state,
                                               self._ledger_norm(oracle),
                                               partials_fn=self._partials,
                                               loss=self.loss)
        return self

    def path(self, X, y, lams, groups=None, mode: str = 'auto',
             hybrid_prefix: int | None = None) -> list[PathPoint]:
        """Fit a regularization path over `lams`; one PathPoint per lambda.

        Args:
          lams: lambda values, any order (duplicates allowed); each must
            be finite and > 0, rejected with a clear error otherwise.
          mode: 'vmap' | 'sequential' | 'auto' (`core.bmrm.bmrm_path`) —
            * 'vmap': the whole sweep is ONE batched device program: a
              (K, ...)-leading bundle state trains every lambda
              simultaneously, per-lambda done masks freezing converged
              slices (DESIGN.md §7). Trades memory (K plane buffers of
              max_planes x n floats each, `core.bmrm.path_state_gib`) for
              full device parallelism.
            * 'sequential': one fit per lambda, warm-started — the device
              solver carries the bundle state across lambdas (cutting
              planes under-estimate R_emp independently of lambda), the
              host solver seeds each fit with the previous w.
            * 'hybrid': sequential-warm the first `hybrid_prefix`
              lambdas (default core.bmrm.DEFAULT_HYBRID_PREFIX = 2),
              then broadcast the last prefix fit's plane buffer as every
              remaining lambda's initial batched state — the batched
              sweep's parallel width WITH (part of) the sequential
              sweep's warm-start saving (EXPERIMENTS §Path sweep).
            * 'auto' (default): vmap for fused device-solver oracles
              (tree/pairs/grouped/sharded above the f32 eps floor) on
              accelerator backends, whose projected batched state fits
              `memory_budget` (when set); sequential on the serial CPU
              backend (where the batched sweep measures 2-8x slower,
              EXPERIMENTS §Path sweep), for streaming and CPU-CSR
              host-rmatvec oracles, and — with a loud RuntimeWarning —
              when the vmap state projects over budget.

        Leaves the estimator fitted at the LAST lambda in `lams`. Each
        PathPoint's report carries per-lambda iterations/objective/gap; in
        vmap mode `seconds` is the lambda's share of the one joint program
        (each batched step's wall splits evenly over the lambdas active in
        it, so the shares sum to ~the sweep's wall-clock).
        """
        # Validate BEFORE oracle construction (a sharded oracle densifies
        # and transfers X — a typo'd mode must not pay for that), via the
        # same bmrm helpers bmrm_path re-runs idempotently: one source of
        # truth for the error messages. lams are also normalized here for
        # the PathPoint zip below.
        _validate_path_mode(mode)
        lams = _validate_lams(lams)
        store, y, groups = self._as_store(X, y, groups)
        oracle = self._make_oracle(X if not isinstance(X, BlockStore)
                                   else store, y, groups)
        self.oracle_ = oracle

        from .bmrm import DEFAULT_HYBRID_PREFIX
        results = bmrm_path(
            oracle, lams, mode=mode, eps=self.eps, max_iter=self.max_iter,
            max_planes=self.max_planes, solver=self.solver,
            sync_every=self.sync_every, qp_iters=self.qp_iters,
            memory_budget=self.memory_budget,
            hybrid_prefix=(DEFAULT_HYBRID_PREFIX if hybrid_prefix is None
                           else int(hybrid_prefix)),
            callback=(lambda t, w, j, g:
                      print(f'  bmrm it={t} J_best={np.asarray(j)} '
                            f'gap={np.asarray(g)}'))
            if self.verbose else None)
        points = [PathPoint(lam=lam, w=res.w,
                            report=self._report(res, res.stats.seconds))
                  for lam, res in zip(lams, results)]
        last = points[-1]
        self.w_, self.report_ = last.w, last.report
        self.lam = last.lam
        self.incremental_ = IncrementalFit(store, results[-1].state,
                                           self._ledger_norm(oracle),
                                           partials_fn=self._partials,
                                           loss=self.loss)
        return points

    def refit(self, X=None, y=None, groups=None, *, retire=(),
              mode: str = 'auto', weight_store=None) -> RefitReport:
        """Incrementally retrain after a data change (DESIGN.md §11).

        Appends one row block (X, y[, groups]) and/or retires previously
        appended blocks by id, then re-solves WARM instead of cold:

          mode='ledger'  revalidate every retained cutting plane against
                         the changed rows only (O(planes·Δ) oracle work,
                         `core.incremental.PlaneLedger`) and re-enter the
                         device driver with the full plane buffer + the
                         previous dual. Requires a device-driver fit (the
                         host driver keeps no bundle state).
          mode='w-only'  drop the planes; warm-start from the previous
                         weight vector alone. Cheaper per refit call
                         (zero revalidation work), more solve iterations.
          mode='auto'    (default) 'ledger' when a ledger exists, the
                         merged oracle can run the device driver, and no
                         retired block belongs to the base component
                         (whose planes are not per-block subtractable);
                         'w-only' otherwise.

        Returns a `RefitReport`; also refreshes `w_` / `report_` /
        `refit_report_` and, when `weight_store` is given (a
        `serve.WeightStore` or a `serve.RankingService`), atomically
        hot-swaps the refreshed weights into it — the full
        train→refit→serve loop in one call.
        """
        if self.incremental_ is None:
            raise RuntimeError('fit() first — refit() continues a fitted '
                               'model')
        if mode not in REFIT_MODES:
            raise ValueError(f'unknown refit mode {mode!r}; expected one '
                             f'of {REFIT_MODES}')
        if mode == 'ledger' and self.loss not in LEDGER_LOSSES:
            raise ValueError(
                f"mode='ledger' is unavailable for loss={self.loss!r}: "
                'its position weights depend on merged within-group '
                'utility ranks, so retained planes are not per-block '
                'revalidatable (core.incremental.LEDGER_LOSSES); refit '
                "with mode='w-only' (mode='auto' does so automatically)")
        inc = self.incremental_
        retire = ((int(retire),) if isinstance(retire, (int, np.integer))
                  else tuple(int(b) for b in retire))
        if X is None and not retire:
            raise ValueError('refit() needs a block to append (X, y) '
                             'and/or block ids to retire')
        if (X is None) != (y is None):
            raise ValueError('append needs both X and y')

        resolved = mode
        if resolved != 'w-only' and inc.ledger is None:
            if resolved == 'ledger':
                raise ValueError(
                    "mode='ledger' needs a device-driver fitted bundle "
                    'state (the host driver keeps none); refit with '
                    "mode='w-only' or fit with solver='device'")
            resolved = 'w-only'
        if resolved == 'auto':
            if any(b in inc.ledger.base_bids for b in retire):
                # Base-component planes are not per-block subtractable;
                # mode='ledger' would rebuild partials over every
                # survivor (O(planes·m_surviving)) — under 'auto' the
                # w-only warm start is the better default.
                resolved = 'w-only'
            else:
                resolved = 'ledger'
        if resolved == 'w-only':
            inc.ledger = None          # drop the planes: w-only contract

        inc.revalidate_seconds = 0.0
        for bid in retire:
            inc.retire(bid)
        appended, delta_rows = (), 0
        if X is not None:
            bid = inc.append(X, y, groups)
            appended = (bid,)
            delta_rows = inc.store.member(bid).source.m
        if not inc.store.block_ids:
            raise ValueError('refit retired every block; nothing left to '
                             'train on')

        store = inc.store
        oracle = self._make_oracle(store, store.y, store.groups)
        self.oracle_ = oracle

        if resolved == 'ledger' and not self._device_solvable(oracle):
            if mode == 'ledger':
                raise ValueError(
                    "mode='ledger' needs the device driver, but the "
                    f'merged {type(oracle).__name__} cannot run it under '
                    f"solver={self.solver!r} (eps={self.eps:g}); use "
                    "mode='w-only'")
            resolved = 'w-only'
            inc.ledger = None

        K = (int(self.max_planes) if self.max_planes is not None
             else DEFAULT_MAX_PLANES)
        t0 = time.perf_counter()
        if resolved == 'ledger':
            state = inc.warm_state(int(oracle.n), K, w0=self.w_)
            if state is None:           # e.g. the ledger lost all pairs
                resolved = 'w-only'
        if resolved == 'ledger':
            n_planes = int(state.n_active)
            res = self._solve(oracle, self.lam, state=state)
        else:
            n_planes = 0
            res = self._solve(oracle, self.lam, w0=self.w_)
        dt = time.perf_counter() - t0

        inc.commit(res.state, self._ledger_norm(oracle))
        self.w_ = res.w
        self.report_ = self._report(res, dt)
        self.refit_report_ = RefitReport(
            mode=resolved, appended=appended, retired=retire,
            n_planes=n_planes, delta_rows=delta_rows,
            revalidate_seconds=inc.revalidate_seconds, fit=self.report_)
        if weight_store is not None:
            if hasattr(weight_store, 'swap_weights'):   # RankingService
                weight_store.swap_weights(self)
            else:                                       # WeightStore
                weight_store.swap(self)
        return self.refit_report_

    def decision_function(self, X) -> np.ndarray:
        if self.w_ is None:
            raise RuntimeError('fit() first')
        return _matvec(X, self.w_)

    def predict(self, X) -> np.ndarray:
        return self.decision_function(X)

    def scorer(self, **kwargs):
        """A `repro.serve.Scorer` over the fitted weights — the serving
        hot path (jitted, shape-bucketed, see `repro.serve`). Kwargs pass
        through to the `Scorer` constructor (`min_bucket`, `donate`).
        Cached per fitted weight vector when called without kwargs;
        refit invalidates the cache."""
        if self.w_ is None:
            raise RuntimeError('fit() first')
        from ..serve import Scorer     # serving layer is optional at import
        if kwargs:
            return Scorer(self.w_, **kwargs)
        cached = getattr(self, '_scorer_cache', None)
        if cached is None or cached[0] is not self.w_:
            self._scorer_cache = (self.w_, Scorer(self.w_))
        return self._scorer_cache[1]

    def scores(self, X) -> np.ndarray:
        """Candidate scores X @ w via the serving scorer (float32 device
        matmul, default buckets) — so notebooks don't hand-roll `X @ w`.
        Sparse inputs fall back to the layout-native
        `decision_function` (the serve hot path is dense)."""
        if self.w_ is None:
            raise RuntimeError('fit() first')
        if hasattr(X, 'matvec') or not hasattr(X, '__array__'):
            return self.decision_function(X)
        return self.scorer().scores(np.asarray(X, np.float32))

    def top_k(self, X, k: int):
        """Best-k candidates by score: `(values, indices)`, ties broken
        lowest-index-first, bit-consistent with ranking `self.scores(X)`
        by a stable full argsort; `k` larger than the candidate count
        returns everything ranked (`repro.serve.Scorer.top_k`)."""
        if self.w_ is None:
            raise RuntimeError('fit() first')
        return self.scorer().top_k(np.asarray(X, np.float32), k)

    def ranking_error(self, X, y, groups=None) -> float:
        """Pairwise ranking error (paper eq. 1) on held-out data."""
        p = jnp.asarray(self.decision_function(X), jnp.float32)
        g = None if groups is None else jnp.asarray(
            np.asarray(groups, np.int32))
        return float(_rank_loss.ranking_error(p, jnp.asarray(y, jnp.float32),
                                              g))

    def objective(self, X, y, groups=None) -> float:
        """J(w) = R_emp(w) + lam ||w||^2 under THIS estimator's loss
        (`core.oracle.empirical_risk`)."""
        p = self.decision_function(X)
        g = None if groups is None else np.asarray(groups, np.int32)
        return (empirical_risk(p, y, g, loss=self.loss)
                + self.lam * float(self.w_ @ self.w_))

    # -- internals ---------------------------------------------------------

    def _as_store(self, X, y, groups):
        """Normalize fit input to (BlockStore, y, groups). A raw X
        becomes block 0 of a fresh store (sources wrap without copying);
        a BlockStore passes through and carries its own y/groups."""
        if isinstance(X, BlockStore):
            if y is not None or groups is not None:
                raise ValueError('a BlockStore carries its own y/groups; '
                                 'do not pass them separately')
            if not X.block_ids:
                raise ValueError('cannot fit an empty BlockStore')
            return X, X.y, X.groups
        if y is None:
            raise ValueError('y is required (omit it only when X is a '
                             'BlockStore)')
        store = BlockStore()
        store.append(X, y, groups)
        return store, y, groups

    def _partials(self, Xb, yb, gb, S):
        """Per-block plane partials with this estimator's engine/loss
        knobs (the `IncrementalFit` revalidation hook)."""
        return block_partials(Xb, yb, gb, S, engine=self.engine,
                              pair_block=self.pair_block, loss=self.loss)

    def _ledger_norm(self, oracle) -> int:
        """The normalizer `IncrementalFit` keys its plane ledger on: the
        oracle's loss norm (N / N+), or 0 for losses with no per-block
        plane decomposition — which disables the ledger entirely, so
        refits warm-start from w alone (LEDGER_LOSSES)."""
        if self.loss not in LEDGER_LOSSES:
            return 0
        return int(oracle.norm)

    def _device_solvable(self, oracle) -> bool:
        """Would `_solve` run this oracle on the device driver? Mirrors
        `core.bmrm.bmrm`'s dispatch — plane-ledger warm starts are
        bundle-state warm starts, which only the device driver accepts."""
        from .bmrm import F32_EPS_FLOOR
        capable = bool(getattr(oracle, 'supports_device_solver', False))
        if self.solver == 'device':
            return capable
        return (self.solver == 'auto' and capable
                and getattr(oracle, 'prefer_device_solver', True)
                and self.eps >= F32_EPS_FLOOR)

    def _make_oracle(self, X, y, groups):
        if isinstance(X, BlockStore):
            # Fused methods need one materialized X; method='auto' keeps
            # the store streaming only when it projects over budget
            # (mirroring make_oracle's own budget rule — a small in-RAM
            # store merges into the faster fused oracle).
            if self.method in ('tree', 'pairs') or (
                    self.method == 'auto' and not X.disk_backed and (
                        self.memory_budget is None
                        or projected_resident_gib(X)
                        <= self.memory_budget)):
                X = X.materialize()
        return make_oracle(X, y, groups=groups, method=self.method,
                           loss=self.loss, engine=self.engine,
                           pair_block=self.pair_block, mesh=self.mesh,
                           memory_budget=self.memory_budget,
                           stream_block=self.stream_block,
                           prefetch=self.prefetch)

    def _solve(self, oracle, lam, state=None, w0=None):
        return bmrm(oracle, lam=lam, eps=self.eps, max_iter=self.max_iter,
                    solver=self.solver, max_planes=self.max_planes,
                    sync_every=self.sync_every, qp_iters=self.qp_iters,
                    state=state, w0=w0,
                    callback=(lambda t, w, j, g:
                              print(f'  bmrm it={t} J_best={j:.6f} '
                                    f'gap={g:.2e}'))
                    if self.verbose else None)

    @staticmethod
    def _report(res, seconds) -> FitReport:
        st = res.stats
        return FitReport(
            iterations=st.iterations, converged=st.converged,
            objective=st.obj_best, gap=st.gap, seconds=seconds,
            oracle_seconds_mean=float(np.mean(st.oracle_seconds))
            if st.oracle_seconds else float('nan'),
            loss_history=st.loss_history, solver=st.solver)
