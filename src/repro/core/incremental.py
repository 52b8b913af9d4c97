"""Incremental retraining: warm-start BMRM across data changes.

The bundle method's empirical risk is a sum over preference pairs, so
every cutting plane (a_i, b_i) — a tangent of R_emp at some support
iterate — is itself a (scaled) sum over pairs. That decomposability is
what this module exploits (DESIGN.md §11; the same structure *Direct
Optimization of Ranking Measures* uses for its bundle solver): when the
training set changes by whole row blocks, the retained planes do not
have to be recut from scratch — they can be *revalidated* by evaluating
the oracle ONLY over the changed rows at each plane's stored support
iterate (`BundleState.S`).

The per-plane invariant the `PlaneLedger` stores, per component c
(the base component from the last full solve, plus one entry per block
appended since):

    ell_c[i] + g_c[i] @ (w - S[i])  <=  N_c * R_c(w)     for all w

where N_c counts component c's within-component pairs, R_c its pairwise
hinge risk, g_c[i] = N_c * subgrad_c(S[i]) and ell_c[i] = N_c *
R_c(S[i]). Summing components and dividing by the MERGED data's pair
count N — which counts the pairs that span two components too, so
N >= sum N_c — yields planes that lower-bound the merged risk. Those
cross-component pairs are the gap: for the hinge their loss sum is
convex, and one tangent of it at the last optimum w0 (two oracle
evaluations over the merged rows at w0: with the data's groups, and with
each group split by component) completes every plane; for 'toppush' they
are dropped (nonnegative; bounds stay valid, possibly looser). Both are
exact when groups never span blocks. Appending a Δ-row block
therefore costs O(planes·Δ) oracle work instead of the O(planes·m) a
full replan would; retiring an *appended* block is exact subtraction
(the ledger recomputes sums from its components in canonical order, so
append-then-retire round-trips bit-identically — no `+=` drift).

What is NOT per-block decomposable is the base component: its planes
are tangents of the risk over the whole block set at the last solve,
cross-block pairs included. Retiring one of ITS blocks cannot be a
subtraction; the ledger rebuilds per-block partials over the survivors
(O(planes·m_surviving)) — or the caller takes the `mode='w-only'`
fallback, which drops the planes and warm-starts from the weight vector
alone (`RankSVM.refit`).

`IncrementalFit` packages the state machine (`data.rowblocks.BlockStore`
+ `PlaneLedger` + the last fitted `BundleState`); `RankSVM.refit` is the
user-facing wiring through oracle dispatch, the device driver, and
serving hot-swap. `refit_chunk_step` adapts one jitted device chunk to
the fault-tolerant runtime loop's step contract so long refits compose
with checkpointed resume (`runtime.loop.run`).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

import jax.numpy as jnp

from ..data.rowblocks import BlockStore
from .bmrm import (DEFAULT_MAX_PLANES, BundleState, _device_chunk,
                   bundle_state_from_planes, f32)
from .oracle import (_loss_norm_weights, _validate_loss, make_oracle)

# Losses whose cross-component loss sum (merged minus within-component)
# is convex, so a tangent of it lower-bounds it: the hinge's, a sum of
# pair hinges. Not 'toppush': a merged anchor's loss minus its block
# loss is a difference of maxima.
CROSS_TANGENT_LOSSES = ('hinge',)

# Losses whose planes ARE per-block decomposable (the ledger contract):
# the component tangent must lower-bound the component's UNNORMALIZED
# merged-risk contribution. True for 'hinge' (a block's pairs are a
# subset of the merged pairs, pair losses nonnegative) and for 'toppush'
# (merging only grows each anchored example's strictly-lower set, and a
# running max over a superset is no smaller — block terms only
# underestimate). FALSE for 'poshinge': its weights v_i = 1/log2(1+rank)
# depend on the example's utility rank WITHIN THE MERGED GROUP, and a
# block-local rank is an underestimate, so block-local weights
# overestimate the merged ones — block planes would over-bound the
# merged risk. `RankSVM(loss='poshinge')` therefore keeps no ledger and
# refits w-only (DESIGN.md §12).
LEDGER_LOSSES = ('hinge', 'toppush')


class BaseRetireError(ValueError):
    """Raised by `PlaneLedger.retire_block` for a block covered by the
    base component, whose planes are not per-block decomposable — the
    caller must rebuild over the survivors or fall back to w-only."""


@dataclasses.dataclass(frozen=True)
class LedgerBlock:
    """One component's per-plane partial sums at the stored iterates.

    `ell[i] = n_pairs * R_block(S[i])` and `g[i] = n_pairs *
    subgrad_block(S[i])` — the unnormalized tangent of this component's
    risk at support iterate i. `n_pairs` counts only within-component
    preference pairs.
    """

    ell: np.ndarray        # (P,)   float64
    g: np.ndarray          # (P, n) float64
    n_pairs: int


def block_partials(X, y, groups, S, *, engine=None,
                   pair_block: int = 2048,
                   loss: str = 'hinge') -> LedgerBlock:
    """Evaluate one block's `LedgerBlock` at the P stored iterates.

    This is the O(planes·Δ) revalidation kernel: P oracle evaluations
    over ONLY this block's rows. A pairless block (constant y within
    every group) contributes zeros without building an oracle. The
    partials scale by the block's LOSS NORMALIZER (N for the hinge, the
    anchored count N+ for 'toppush' — `oracle._loss_norm_weights`), the
    quantity the ledger's invariant sums over components; 'poshinge' has
    no per-block decomposition (`LEDGER_LOSSES`) and is rejected here.
    """
    _validate_loss(loss)
    if loss not in LEDGER_LOSSES:
        raise ValueError(
            f'loss {loss!r} has no per-block plane decomposition '
            f'(LEDGER_LOSSES = {LEDGER_LOSSES}): its position weights '
            'depend on merged within-group utility ranks, so block-local '
            "partials would over-bound the merged risk; refit with "
            "mode='w-only'")
    y = np.asarray(y)
    S = np.asarray(S, np.float64)
    P, n = S.shape
    norm, _ = _loss_norm_weights(y, groups, loss)
    norm = int(norm)
    if norm == 0 or P == 0:
        return LedgerBlock(np.zeros(P), np.zeros((P, n)), norm)
    # method='auto' keeps in-RAM blocks on the fused oracle and streams
    # RowBlockSource members (memmap blocks never materialize).
    oracle = make_oracle(X, y, groups, method='auto', loss=loss,
                         engine=engine, pair_block=pair_block)
    ell = np.zeros(P)
    g = np.zeros((P, n))
    for i in range(P):
        loss_i, a = oracle.loss_and_subgrad(S[i])
        ell[i] = norm * float(loss_i)
        g[i] = norm * np.asarray(a, np.float64)
    return LedgerBlock(ell, g, norm)


class PlaneLedger:
    """Block-keyed per-plane partial sums behind plane revalidation.

    Components: one `base` (planes read off the last solve's
    `BundleState`, covering every block retained at that solve — cross-
    block pairs included) plus one `LedgerBlock` entry per block appended
    since, in insertion order. `planes()` recomputes the merged (A, b)
    from the components on every call — components are immutable and
    sums are never updated in place, so retiring an appended block
    restores the exact floating-point sequence of the never-appended
    ledger (the bit-identity the tests pin down).
    """

    def __init__(self, S: np.ndarray, alpha: np.ndarray,
                 base: LedgerBlock, base_bids):
        S = np.asarray(S, np.float64)
        alpha = np.asarray(alpha, np.float64).ravel()
        if S.ndim != 2 or alpha.shape != (S.shape[0],):
            raise ValueError(f'iterates S{S.shape} and dual '
                             f'alpha{alpha.shape} do not align')
        if base.ell.shape != (S.shape[0],) or base.g.shape != S.shape:
            raise ValueError('base component does not match the iterates')
        self.S = S
        self.alpha = alpha
        self._base = base
        self._base_bids = frozenset(int(b) for b in base_bids)
        self._entries: dict[int, LedgerBlock] = {}

    @classmethod
    def from_state(cls, state: BundleState, n_pairs: int,
                   block_ids) -> 'PlaneLedger':
        """Read the base component off a fitted device-driver state.

        Zero oracle work: plane i of the state satisfies
        a_i @ w + b_i <= R(w) with tangent point S[i], so the
        unnormalized invariant is g0[i] = N * a_i and
        ell0[i] = N * (b_i + a_i @ S[i]).
        """
        P = int(state.n_active)
        A = np.asarray(state.A, np.float64)[:P]
        b = np.asarray(state.b, np.float64)[:P]
        S = np.asarray(state.S, np.float64)[:P]
        alpha = np.asarray(state.alpha, np.float64)[:P]
        N = float(int(n_pairs))
        g0 = N * A
        ell0 = N * (b + np.einsum('ij,ij->i', A, S))
        return cls(S, alpha, LedgerBlock(ell0, g0, int(n_pairs)),
                   block_ids)

    @property
    def n_planes(self) -> int:
        return int(self.S.shape[0])

    @property
    def base_bids(self) -> frozenset:
        return self._base_bids

    @property
    def entry_bids(self) -> tuple:
        return tuple(self._entries)

    @property
    def n_pairs(self) -> int:
        """Merged pair count (cross-component pairs excluded — they are
        the dropped, not double-counted, part of the bound)."""
        return self._base.n_pairs + sum(
            e.n_pairs for e in self._entries.values())

    def covers(self, bid: int) -> bool:
        return bid in self._base_bids or bid in self._entries

    def append_block(self, bid: int, block: LedgerBlock):
        bid = int(bid)
        if self.covers(bid):
            raise ValueError(f'block {bid} is already in the ledger')
        if block.ell.shape != (self.n_planes,) or (
                block.g.shape != self.S.shape):
            raise ValueError(f'block partials ell{block.ell.shape}/'
                             f'g{block.g.shape} do not match the '
                             f'{self.n_planes}-plane ledger')
        self._entries[bid] = block

    def retire_block(self, bid: int):
        bid = int(bid)
        if bid in self._base_bids:
            raise BaseRetireError(
                f'block {bid} is part of the base component (planes from '
                'the last solve are tangents of the risk over ALL blocks '
                'retained then, cross-block pairs included) and cannot be '
                'subtracted out — rebuild per-block partials over the '
                "survivors or refit with mode='w-only'")
        if bid not in self._entries:
            raise ValueError(f'block {bid} is not in the ledger; entries: '
                             f'{sorted(self._entries)}')
        del self._entries[bid]

    def planes(self, norm: 'int | None' = None,
               cross=None) -> tuple[np.ndarray, np.ndarray]:
        """Merged (A, b) for the current component set, float64.

        A[i] = (sum of g components)[i] / N and b[i] recovers the offset
        at the stored tangent point: b[i] = ell_merged[i]/N - A[i] @ S[i].
        N is `norm`, the merged data's loss normalizer, which counts the
        pairs that span components too; it defaults to the components'
        own `n_pairs`, right only when no pair spans two components.
        `cross = (ell_x, g_x, w_x)` adds to every plane one unnormalized
        tangent of the cross-component loss sum, taken at w_x.
        Summation runs over components in canonical insertion order
        starting from copies of the base — never in place — so the
        result for a given component set is a pure function of that set
        (bit-identical round trips).
        """
        N = float(self.n_pairs if norm is None else norm)
        if N <= 0:
            raise ValueError('ledger covers no preference pairs; nothing '
                             'to build planes from')
        if N < self.n_pairs:
            raise ValueError(f'merged normalizer {norm} is below the '
                             f"components' own {self.n_pairs}")
        ell = self._base.ell.copy()
        g = self._base.g.copy()
        for e in self._entries.values():
            ell = ell + e.ell
            g = g + e.g
        A = g / N
        b = ell / N - np.einsum('ij,ij->i', A, self.S)
        if cross is not None:
            ell_x, g_x, w_x = cross
            g_x = np.asarray(g_x, np.float64)
            A = A + g_x / N
            b = b + (float(ell_x) - float(g_x @ np.asarray(w_x))) / N
        return A, b


@dataclasses.dataclass
class RefitReport:
    """What one `RankSVM.refit` did and what it cost."""

    mode: str                    # 'ledger' | 'w-only' (as resolved)
    appended: tuple              # block ids appended by this call
    retired: tuple               # block ids retired by this call
    n_planes: int                # planes carried into the warm start
    delta_rows: int              # rows revalidated against (appended)
    revalidate_seconds: float    # host time spent on block partials
    fit: object = None           # the warm solve's FitReport


class IncrementalFit:
    """State machine of data-warm-started refits.

    Owns the `BlockStore` (the data), the `PlaneLedger` (revalidated
    planes; None when the last fit ran on the host driver, which keeps
    no bundle state), and the last fitted `BundleState`. `RankSVM.fit`
    creates one; `RankSVM.refit` drives it. Usable standalone for custom
    training loops: append/retire, then `warm_state()` to seed the
    device driver, then `commit()` with the solved state.
    """

    def __init__(self, store: BlockStore, state: 'BundleState | None',
                 n_pairs: int, partials_fn=None, loss: str = 'hinge'):
        self.store = store
        self.state = state
        self._partials_fn = partials_fn or block_partials
        self.loss = loss
        self.revalidate_seconds = 0.0
        self.ledger = None
        if state is not None and int(state.n_active) > 0 and n_pairs > 0:
            self.ledger = PlaneLedger.from_state(state, n_pairs,
                                                 store.block_ids)

    def append(self, X, y, groups=None) -> int:
        """Append a block to the store and revalidate every retained
        plane against it (O(planes·Δ) oracle work; zero when there is
        no ledger to maintain)."""
        bid = self.store.append(X, y, groups)
        if self.ledger is not None:
            mem = self.store.member(bid)
            t0 = time.perf_counter()
            self.ledger.append_block(
                bid, self._partials_fn(mem.source, mem.y, mem.groups,
                                       self.ledger.S))
            self.revalidate_seconds += time.perf_counter() - t0
        return bid

    def retire(self, bid: int):
        """Retire a block. For a block appended since the last solve the
        ledger subtracts it exactly; for a base-component block the
        ledger is rebuilt per-block over the survivors
        (O(planes·m_surviving) — the documented cost of base retires;
        `RankSVM.refit(mode='auto')` prefers w-only in that case)."""
        self.store.retire(bid)
        if self.ledger is None:
            return
        try:
            self.ledger.retire_block(bid)
        except BaseRetireError:
            self._rebuild()

    def _rebuild(self):
        """Decompose the surviving blocks into per-block entries at the
        stored iterates: an empty base plus one freshly evaluated
        `LedgerBlock` per block. Cross-block pair losses drop (bounds
        loosen but stay valid)."""
        S, alpha = self.ledger.S, self.ledger.alpha
        P, n = S.shape
        led = PlaneLedger(S, alpha,
                          LedgerBlock(np.zeros(P), np.zeros((P, n)), 0),
                          frozenset())
        t0 = time.perf_counter()
        for bid in self.store.block_ids:
            mem = self.store.member(bid)
            led.append_block(bid, self._partials_fn(mem.source, mem.y,
                                                    mem.groups, S))
        self.revalidate_seconds += time.perf_counter() - t0
        self.ledger = led

    def planes(self, w0=None) -> tuple[np.ndarray, np.ndarray]:
        """The ledger's planes for the retained blocks' merged risk.

        They are normalized by the merged loss normalizer, which counts
        the pairs that span two components (the base, each appended
        block). For the hinge, given w0, each plane also carries the
        tangent at w0 of those pairs' loss sum: the merged partials at
        w0 less the partials with every group split by component. That
        is two oracle evaluations over the merged rows, O(m log m), once
        per refit."""
        store, led = self.store, self.ledger
        W = (np.zeros((0, led.S.shape[1])) if w0 is None
             else np.asarray(w0, np.float64).reshape(1, -1))
        merged = self._partials_fn(store, store.y, store.groups, W)
        cross = None
        if (w0 is not None and self.loss in CROSS_TANGENT_LOSSES
                and merged.n_pairs > led.n_pairs):
            comp = np.concatenate([
                np.full(store.member(bid).source.m,
                        0 if bid in led.base_bids else 1 + k)
                for k, bid in enumerate(store.block_ids)])
            split = comp if store.groups is None else (
                np.unique(store.groups, return_inverse=True)[1].ravel()
                * (len(store.block_ids) + 1) + comp)
            within = self._partials_fn(store, store.y, split, W)
            cross = (merged.ell[0] - within.ell[0],
                     merged.g[0] - within.g[0], W[0])
        return led.planes(merged.n_pairs, cross)

    def warm_state(self, dim: int, max_planes: int,
                   w0=None) -> 'BundleState | None':
        """The revalidated planes (`planes(w0)`) as a device-driver warm
        start, or None when there is nothing to warm from (no ledger, no
        planes, or no pairs). Past `max_planes` the highest-dual-weight
        planes are kept (the dual says which planes the last optimum
        leaned on)."""
        if self.ledger is None or self.ledger.n_planes == 0:
            return None
        if self.ledger.n_pairs <= 0:
            return None
        A, b = self.planes(w0)
        S, alpha = self.ledger.S, self.ledger.alpha
        K = int(max_planes)
        if A.shape[0] > K:
            keep = np.sort(np.argsort(alpha)[::-1][:K])
            A, b, S, alpha = A[keep], b[keep], S[keep], alpha[keep]
        return bundle_state_from_planes(A, b, S, dim, K, w0=w0,
                                        alpha=alpha)

    def commit(self, state: 'BundleState | None', n_pairs: int):
        """Adopt a finished solve: its planes become the new base
        component (they cover every currently retained block) and the
        appended-entry list resets."""
        self.state = state
        self.ledger = None
        if state is not None and int(state.n_active) > 0 and n_pairs > 0:
            self.ledger = PlaneLedger.from_state(state, n_pairs,
                                                 self.store.block_ids)


def refit_chunk_step(oracle, lam: float, eps: float, *,
                     max_planes: 'int | None' = None, sync_every: int = 8,
                     qp_iters: int = 128):
    """Adapt one jitted device chunk to `runtime.loop.run`'s step
    contract, so a long (re)fit composes with checkpointed resume.

    Returns `step(state, batch) -> (state, metrics)` where `state` is a
    `BundleState` (checkpointable pytree) and `metrics['loss']` is the
    running best objective (finite after the first chunk, as the loop
    requires). `batch` is ignored — the oracle owns its data — so drive
    it with `batch_fn=lambda step: None`. Resume mid-refit restores the
    exact bundle state: planes, dual, iterates and all.
    """
    K = int(max_planes) if max_planes is not None else DEFAULT_MAX_PLANES
    chunk = _device_chunk(oracle, K, max(1, int(sync_every)),
                          int(qp_iters))
    lam_d = jnp.asarray(lam, f32)
    eps_d = jnp.asarray(eps, f32)

    def step(state: BundleState, batch):
        del batch
        state, (_losses, _gaps, _valids) = chunk(state, lam_d, eps_d)
        return state, {'loss': state.j_best, 'gap': state.gap}

    return step
