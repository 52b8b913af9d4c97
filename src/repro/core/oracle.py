"""The BMRM oracle layer: one device-resident (loss, subgradient) abstraction.

Every RankSVM training path — the paper's merge-sort-tree sweep, the O(m^2)
pairwise baseline, the Pallas kernel fast path, per-query LTR grouping, the
pod-scale sharded oracle, and the out-of-core streaming oracle over row-block
feature sources — is a `RankOracle`: an object that evaluates

    loss_and_subgrad(w) -> (R_emp(w), a)      a = X^T (c - d) / N   (Lemma 2)

plus the metadata BMRM needs (m, n, exact pair count N, device-residency).
`core.bmrm` consumes any RankOracle; `core.ranksvm` is a thin estimator that
selects one. New backends are one new subclass, not another estimator fork.

Device-residency (DESIGN.md §4): each oracle's matvec + counts + loss +
subgradient run as ONE jitted function — `p`, `c - d`, and the plane
gradient `a` stay on device, eliminating the per-iteration host<->device
round-trips of the pre-refactor estimator (`RankSVM._counts`). The single
exception is measured, not assumed: on the CPU backend XLA's scatter-add is
~2.5x slower than numpy's bincount loop, so the CSR transpose-matvec of the
subgradient dispatches to the host kernel there (`csr_rmatvec='auto'`); on
accelerator backends it stays on device. Either way the O(m log^2 m) counts
and the forward matvec are device-side, and only w (in) and (loss, a) (out)
cross the boundary.

Tree counts use `counts.counts_fused` — the single-tree variant (one
argsort + one merge-sort-tree build per oracle call instead of two) —
except where a different counting engine is the point (PairwiseOracle's
blocked pass and its `counts_auto` Pallas-kernel dispatch).
"""

from __future__ import annotations

import functools
import warnings

import numpy as np

try:
    import scipy.sparse as _scipy_sparse
except Exception:  # pragma: no cover - scipy is installed in this container
    _scipy_sparse = None

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from . import counts as _counts
from . import distributed as _dist
from ..data import rowblocks as _rowblocks
from ..data.rowblocks import _validate_block_rows as _validate_block
from ..data.rowblocks import _validate_prefetch, resolve_prefetch
from ..kernels.platform import device_platform as _device_platform

f32 = jnp.float32


# ------------------------------------------------------------------ losses


LOSSES = ('hinge', 'toppush', 'poshinge')


def _validate_loss(loss: str) -> None:
    """Reject typo'd loss names at the dispatch boundary (same contract as
    `counts._validate_engine`): an unknown loss must fail before any oracle
    construction, densify, or device transfer happens."""
    if loss not in LOSSES:
        raise ValueError(f'unknown loss {loss!r}; '
                         f'expected one of {LOSSES}')


def _toppush_norm(y: np.ndarray, groups) -> int:
    """Exact host count of ANCHORED examples — those with at least one
    strictly-lower-utility example in their group — the TopPush loss
    normalizer (each anchored example contributes one hinge term)."""
    y = np.asarray(y)
    if y.size == 0:
        return 0
    if groups is None:
        return int(np.sum(y > y.min()))
    groups = np.asarray(groups)
    return int(sum(np.sum(y[groups == u] > y[groups == u].min())
                   for u in np.unique(groups)))


def _poshinge_weights_norm(y: np.ndarray, groups):
    """(v, W) for the position-weighted hinge, exact on host.

    v_i = 1 / log2(1 + rank_i) with rank_i = |{k in group : y_k > y_i}| + 1
    — the DCG-style decay of example i's UTILITY rank (a static function
    of y, which is what keeps the loss convex in w; a score-rank weight
    would not be). W = sum over preference pairs (i, j), y_i < y_j, of the
    higher-utility side's weight v_j — the normalizer that replaces N.
    O(m log m): one sort + two searchsorteds per group.
    """
    y = np.asarray(y, np.float64)
    m = y.shape[0]
    v = np.zeros(m)
    W = 0.0
    gs = (np.zeros(m, np.int64) if groups is None
          else np.asarray(groups, np.int64))
    for u in np.unique(gs):
        mask = gs == u
        yy = y[mask]
        ys = np.sort(yy)
        rank = (yy.shape[0]
                - np.searchsorted(ys, yy, side='right')) + 1
        vv = 1.0 / np.log2(1.0 + rank)
        v[mask] = vv
        lower = np.searchsorted(ys, yy, side='left')   # strictly-lower count
        W += float(np.sum(vv * lower))
    return v, W


def _loss_norm_weights(y, groups, loss: str):
    """(norm, v): the loss normalizer (exact, host) and the per-example
    weight vector (None except for 'poshinge').

      loss        norm                              weights
      'hinge'     N  = exact preference-pair count  —
      'toppush'   N+ = anchored-example count       —
      'poshinge'  W  = sum of pair weights v_j      v (float64)

    For any fixed (y, groups) the three norms are zero simultaneously
    (each needs at least one within-group strict-utility pair), so the
    oracles' no-pairs gate applies to every loss unchanged.
    """
    if loss == 'toppush':
        return _toppush_norm(y, groups), None
    if loss == 'poshinge':
        v, W = _poshinge_weights_norm(y, groups)
        return W, v
    return _exact_pairs(y, groups), None


# --------------------------------------------------------------- interface


class RankOracle:
    """Interface: per-iteration (loss, subgradient) for BMRM (Algorithm 1).

    Attributes:
      m: number of training examples (rows of X).
      n: feature dimension (= dim of w and of the subgradient).
      n_pairs: exact number of preference pairs N (host int).
      norm: the LOSS normalizer (host scalar): N for the uniform hinge,
        the anchored-example count N+ for 'toppush', the pair-weight sum
        W for 'poshinge' (`_loss_norm_weights`). Equals n_pairs for the
        hinge; the plane ledger scales by THIS, not n_pairs
        (core.incremental).
      device_resident: True when the subgradient comes out of a fused jitted
        step — bmrm then keeps its cutting-plane bookkeeping on device.
      supports_device_solver: True when `step_fn` yields a traced step that
        bmrm's device driver can fuse into its jitted bundle_step.
      prefer_device_solver: the bmrm solver='auto' hint — True when fusing
        the whole iteration on device is the measured win for this oracle's
        layout/backend. False e.g. for CSR features whose transpose-matvec
        dispatches to the host kernel (DESIGN.md §4): the device driver
        would force the slower on-device scatter.
      supports_path_vmap: True when `step_fn` is vmappable over the iterate
        w, so `bmrm_path(mode='vmap')` can batch a whole regularization
        path into one device program (DESIGN.md §7). True for the fused
        and sharded oracles (pure traced jax); False for the streaming
        oracle, whose `jax.pure_callback` block fetches have no batching
        rule — path mode='auto' keeps it on the sequential warm-started
        sweep.
      name: short identifier for reports/benchmarks.
    """

    name = 'abstract'
    device_resident = False
    supports_device_solver = False
    prefer_device_solver = False
    supports_path_vmap = False
    loss = 'hinge'
    m: int
    n: int
    n_pairs: int
    norm: float

    def loss_and_subgrad(self, w):
        """R_emp(w) and a subgradient of R_emp at w (Lemmas 1-2)."""
        raise NotImplementedError

    def step_fn(self):
        """A purely-traced `w -> (R_emp(w), a)` closure, composable inside
        an outer jit (bmrm's device driver). Only oracles with
        `supports_device_solver` provide one."""
        raise NotImplementedError(
            f'{type(self).__name__} has no traced step_fn; use the host '
            'BMRM driver')


def _exact_pairs(y: np.ndarray, groups) -> int:
    if groups is None:
        return _counts.num_pairs_host(y)
    groups = np.asarray(groups)
    return int(sum(_counts.num_pairs_host(y[groups == u])
                   for u in np.unique(groups)))


def _validate_groups(groups, m: int) -> np.ndarray:
    """Validate user-supplied group ids; returns them compact-relabelled
    onto [0, n_groups) as an int32 vector.

    Group ids feed the key-offset trick (counts._group_offsets), where a NaN
    poisons every offset key and a fractional id silently merges or splits
    queries — both produce wrong counts with no error downstream, so the
    oracle layer rejects them here with actionable messages. The relabel
    matters for the same reason: the offset-key magnitude scales with the
    id VALUES, so hashed/sparse ids (~1e7) would push one f32 ulp of the
    keys past the hinge margin; after it only the group COUNT matters.
    """
    g = np.asarray(groups)
    if g.ndim != 1:
        raise ValueError(f'groups must be 1-D (one id per example); got '
                         f'shape {g.shape}')
    if g.shape[0] != m:
        raise ValueError(f'groups has {g.shape[0]} entries but y has {m} '
                         'examples; they must align one-to-one')
    if g.dtype == np.bool_:
        g = g.astype(np.int32)          # two-query encoding, fine as ids
    if (g.dtype == object or np.issubdtype(g.dtype, np.complexfloating)
            or not np.issubdtype(g.dtype, np.number)):
        raise ValueError(f'groups must be integer ids; got dtype {g.dtype}')
    if np.issubdtype(g.dtype, np.floating):
        if np.isnan(g).any():
            raise ValueError('groups contains NaN; every example needs a '
                             'valid integer group id')
        if np.isinf(g).any():
            raise ValueError('groups contains infinite values; group ids '
                             'must be finite integers')
        if not np.all(g == np.floor(g)):
            raise ValueError('groups contains non-integer values; group '
                             'ids must be (castable to) integers')
    gi = g.astype(np.int64)
    if g.size and not np.array_equal(gi.astype(g.dtype), g):
        raise ValueError('group ids overflow int64; relabel them first '
                         '(e.g. np.unique(groups, return_inverse=True))')
    return np.unique(gi, return_inverse=True)[1].astype(np.int32)


def _warn_group_key_scale(groups: np.ndarray, y: np.ndarray, tol: float,
                          stacklevel: int = 4) -> None:
    """Warn when the f32 key-offset quantization of grouped counting may
    exceed `tol` margin units (hinge margin = 1).

    The offset keys scale as n_groups * (score range + y range + margins);
    the score range is unknown until training, so the y-based estimate is
    a lower bound. `tol` is each oracle's own noise level: ~1e-3 for the
    f32 fused oracles (the counts.py ~1e4-envelope note), ~1e-2 for the
    bf16 sharded oracle.
    """
    if not groups.size:        # m = 0: leave the clean no-pairs error to
        return                 # the n_pairs check downstream
    n_groups = int(groups.max()) + 1
    key_scale = n_groups * (float(y.max() - y.min()) + 3.5)
    ulp = key_scale * 2.0 ** -23
    if ulp > tol:
        warnings.warn(
            f'{n_groups} groups with y-range {float(y.max() - y.min()):.3g}'
            ' push the f32 key-offset keys of grouped counting to a scale '
            f'where one ulp (~{ulp:.1e} margin units) exceeds this '
            f'oracle\'s ~{tol:g} tolerance — counts/subgradients will be '
            'quietly inaccurate. Shrink the y range or split the fit into '
            'fewer-query shards (counts._group_offsets, DESIGN.md §5).',
            RuntimeWarning, stacklevel=stacklevel)


# --------------------------------------------------------- feature engines


def _is_csr_like(X) -> bool:
    return (hasattr(X, 'data') and hasattr(X, 'indices')
            and hasattr(X, 'indptr'))


class _DenseFeatures:
    """Row-major dense X, fully device-resident (both matvecs are gemv;
    the traced math lives in `_fused_step`)."""

    kind = 'dense'
    _uniform = False

    def __init__(self, X):
        self.m, self.n = map(int, X.shape)
        self.arrays = {'X': jnp.asarray(np.asarray(X), f32)}
        self.device_rmatvec = True


class _CSRFeatures:
    """CSR X on device: gather-based forward matvec (a dense (m, s)
    gather+reduce when rows have uniform nnz — the tf-idf layout — else a
    sorted segment-sum), and a backend-dispatched transpose-matvec: XLA
    scatter-add on accelerators, numpy bincount on the CPU backend where
    the measured scatter throughput loses to the host loop.
    """

    kind = 'csr'

    def __init__(self, X, csr_rmatvec: str = 'auto'):
        if _scipy_sparse is not None and _scipy_sparse.issparse(X):
            X = X.tocsr()
        self._host = X
        self.m, self.n = map(int, X.shape)
        data = np.asarray(X.data, np.float32)
        indices = np.asarray(X.indices, np.int32)
        indptr = np.asarray(X.indptr, np.int64)
        lens = np.diff(indptr)
        self._uniform = bool(self.m > 0 and np.all(lens == lens[0])
                             and lens[0] > 0)
        if self._uniform:
            s = int(lens[0])
            self.arrays = {'data2': jnp.asarray(data.reshape(self.m, s)),
                           'idx2': jnp.asarray(indices.reshape(self.m, s))}
        else:
            rows = np.repeat(np.arange(self.m, dtype=np.int32),
                             lens.astype(np.int64))
            self.arrays = {'data': jnp.asarray(data),
                           'idx': jnp.asarray(indices),
                           'rows': jnp.asarray(rows)}
        if csr_rmatvec == 'auto':
            # The actual device platform, not jax.default_backend(): the
            # scatter-vs-bincount trade is a property of the hardware the
            # scatter would run on (kernels.platform, same probe as the
            # Pallas lowering dispatch).
            csr_rmatvec = ('host' if _device_platform() == 'cpu'
                           else 'device')
        if csr_rmatvec not in ('host', 'device'):
            raise ValueError(f'unknown csr_rmatvec {csr_rmatvec!r}')
        self.device_rmatvec = csr_rmatvec == 'device'

    def rmatvec_host(self, v: np.ndarray) -> np.ndarray:
        X = self._host
        if hasattr(X, 'rmatvec'):               # repro.data.sparse.CSRMatrix
            return X.rmatvec(v)
        return np.asarray(X.T @ v).ravel()      # scipy CSR


def _features(X, csr_rmatvec: str = 'auto'):
    if _is_csr_like(X) or (_scipy_sparse is not None
                           and _scipy_sparse.issparse(X)):
        return _CSRFeatures(X, csr_rmatvec=csr_rmatvec)
    return _DenseFeatures(X)


# ----------------------------------------------------- fused device oracles


# Engine dispatch lives with the counting engines now — counts.counts_
# dispatch — so fused and streaming oracles share ONE counting core.


def _toppush_loss_coeffs(p, y, g, inv_n):
    """TopPush-style top-rank loss + subgradient coefficients, one sorted
    pass — NO frequency vectors (DESIGN.md §12).

    Each ANCHORED example i (one with a strictly-lower-utility example in
    its group) is penalized by its margin against the maximum score of
    that strictly-lower set:

        R(w) = (1/N+) sum_i hinge(1 + M_i - p_i),
        M_i  = max{p_k : g_k = g_i, y_k < y_i}

    — for binary y this is exactly TopPush (each positive vs the top
    negative, arxiv 1410.1462), generalized to arbitrary real utilities.
    One stable sort by (g, y) makes every strictly-lower set a prefix of
    its group segment; M comes from a segmented running max
    (`associative_scan`), and the frontier/segment starts from running
    maxima over change-point indices. O(m log m), trivially vmappable.

    The subgradient puts -1 on each active example and +1 on the LEFTMOST
    attaining argmax of its lower set (first new-max event of the
    segmented scan) — a deterministic tie-break reproducible in numpy
    (stable lexsort + first-occurrence argmax), which is what the
    differential tests pin. Returns (loss, coeffs) with
    subgrad = X^T (coeffs * inv_n), the same contract as the counting
    losses.
    """
    m = p.shape[0]
    pf = p.astype(f32)
    yf = y.astype(f32)
    gi = jnp.zeros((m,), jnp.int32) if g is None else g.astype(jnp.int32)
    order = jnp.lexsort((yf, gi))          # stable: ties in original order
    gs = jnp.take(gi, order)
    ys = jnp.take(yf, order)
    ps = jnp.take(pf, order)
    idx = jnp.arange(m, dtype=jnp.int32)
    g_change = jnp.concatenate(
        [jnp.ones((1,), bool), gs[1:] != gs[:-1]]) if m else jnp.zeros(
            (0,), bool)
    key_change = g_change | jnp.concatenate(
        [jnp.ones((1,), bool),
         ys[1:] != ys[:-1]]) if m else g_change
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(g_change, idx, -1))
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(key_change, idx, -1))

    def seg_max(a, b):
        sa, va = a
        sb, vb = b
        return sb, jnp.where(sa == sb, jnp.maximum(va, vb), vb)

    _, running = jax.lax.associative_scan(seg_max, (gs, ps))
    # first index attaining the CURRENT segment max: the last new-max
    # event at or before t (running is nondecreasing within a segment,
    # so ties keep the earliest attaining index)
    prev_run = jnp.concatenate([ps[:1], running[:-1]]) if m else running
    new_max = g_change | (ps > prev_run)
    attain = jax.lax.associative_scan(
        jnp.maximum, jnp.where(new_max, idx, -1))

    fr = run_start                 # strictly-lower prefix is [seg_start, fr)
    anchored = fr > seg_start
    safe = jnp.maximum(fr - 1, 0)
    M = jnp.take(running, safe)
    margin = 1.0 + M - ps
    active = anchored & (margin > 0)
    loss = jnp.sum(jnp.where(active, margin, 0.0)) * inv_n
    amax = jnp.take(attain, safe)
    act = active.astype(f32)
    coeffs = (-act).at[jnp.where(active, amax, 0)].add(act)
    return loss, jnp.zeros((m,), f32).at[order].set(coeffs)


def _loss_and_coeffs(p, y, g, inv_n, v=None, *, engine: str = 'tree',
                     block: int = 0, loss: str = 'hinge'):
    """The shared counting core: scores -> (R_emp, subgradient coefficients).

    Every oracle — fused (`_fused_step_impl`) and streaming
    (`StreamingOracle`, which arrives here with a chunk-accumulated score
    vector) — reduces to this O(m)-resident computation, per loss:

      'hinge'     one counting pass (engine-dispatched; grouped via the
                  key-offset trick) + the Lemma 1/2 formula; coeffs c - d.
      'poshinge'  the weighted counting pass (`counts_dispatch(v=)`):
                  R*W = sum_i ((c~_i - v_i d_i) p_i + c~_i), coeffs
                  c~ - v*d — the Lemma 1/2 identity with the c-side query
                  weighted by the higher-utility side's position decay and
                  the d-side scaled by the example's OWN weight.
      'toppush'   no frequency vectors at all: the one-sorted-pass
                  running-max step (`_toppush_loss_coeffs`); `engine` is
                  inert for it.

    Returns (loss, coeffs as f32); the subgradient is
    X^T (coeffs * inv_n), finished by whichever matvec the caller owns.
    `inv_n` is 1/norm for the oracle's loss (`_loss_norm_weights`); `v`
    is the per-example weight vector (poshinge only, else None).
    """
    if loss == 'toppush':
        return _toppush_loss_coeffs(p, y, g, inv_n)
    if loss == 'poshinge':
        cw, d = _counts.counts_dispatch(p, y, g, engine=engine,
                                        block=block, v=v)
        cd = cw - v.astype(f32) * d.astype(f32)
        return jnp.sum(cd * p + cw) * inv_n, cd
    c, d = _counts.counts_dispatch(p, y, g, engine=engine, block=block)
    cd = (c - d).astype(f32)
    return jnp.sum(cd * p + c.astype(f32)) * inv_n, cd


def _fused_step_impl(w, arrays, y, g, inv_n, pw=None, *, engine: str,
                     block: int, kind: str, uniform: bool, n: int,
                     device_rmatvec: bool, loss: str = 'hinge'):
    """The fused device step: matvec -> counts -> loss -> subgradient.

    Unjitted body so it composes INSIDE a larger traced program — bmrm's
    device driver inlines it into its jitted bundle_step via
    `_FusedOracle.step_fn`. `_fused_step` below is the jitted entry point
    for standalone per-call use (`loss_and_subgrad`). When device_rmatvec
    is False the step returns (loss, coeffs) and the caller finishes the
    transpose-matvec on host (see _CSRFeatures). `pw` is the poshinge
    per-example weight vector (None for the other losses).
    """
    m = y.shape[0]
    if kind == 'dense':
        p = arrays['X'] @ w
    elif uniform:
        p = jnp.sum(arrays['data2'] * w[arrays['idx2']], axis=1)
    else:
        p = jax.ops.segment_sum(arrays['data'] * w[arrays['idx']],
                                arrays['rows'], num_segments=m,
                                indices_are_sorted=True)
    loss_val, cd = _loss_and_coeffs(p, y, g, inv_n, pw, engine=engine,
                                    block=block, loss=loss)
    if not device_rmatvec:
        return loss_val, cd                  # host finishes the rmatvec
    v = cd * inv_n
    if kind == 'dense':
        return loss_val, arrays['X'].T @ v
    if uniform:
        return loss_val, jax.ops.segment_sum(
            (arrays['data2'] * v[:, None]).reshape(-1),
            arrays['idx2'].reshape(-1), num_segments=n)
    return loss_val, jax.ops.segment_sum(arrays['data'] * v[arrays['rows']],
                                         arrays['idx'], num_segments=n)


_fused_step = functools.partial(jax.jit, static_argnames=(
    'engine', 'block', 'kind', 'uniform', 'n',
    'device_rmatvec', 'loss'))(_fused_step_impl)


class _FusedOracle(RankOracle):
    """Shared machinery around `_fused_step`. Subclasses pick the counting
    engine ('tree' | 'blocked' | 'pallas' | 'auto') via `_engine`; an
    explicit `engine=` overrides the subclass default (the
    `make_oracle(engine=)` / `RankSVM(engine=)` pass-through), so e.g.
    the tree oracle swaps its per-iteration counting pass for the fused
    rank-counts Pallas kernel with zero other changes."""

    device_resident = True
    supports_device_solver = True
    supports_path_vmap = True    # pure traced step: vmaps over w cleanly
    # ('pallas' included: rank_counts carries a sequential_vmap rule)
    _engine = 'tree'
    _block = 0          # only meaningful for the blocked engine

    def __init__(self, X, y, groups=None, csr_rmatvec: str = 'auto',
                 engine: str | None = None, engine_block: int = 2048,
                 loss: str = 'hinge'):
        _validate_loss(loss)
        self.loss = loss
        if engine is not None:
            _counts._validate_engine(engine)
            self._engine = engine
            self.name = f'{self.name}[{engine}]'
        if loss != 'hinge':
            self.name = f'{self.name}/{loss}'
        y = np.asarray(y, np.float32)
        self._feats = _features(X, csr_rmatvec=csr_rmatvec)
        self.m, self.n = self._feats.m, self._feats.n
        if y.shape[0] != self.m:
            raise ValueError(f'X has {self.m} rows but y has {y.shape[0]}')
        if groups is not None:
            groups = _validate_groups(groups, self.m)   # compact-relabels
            # ~1e-3 tolerance: counts.py's ~1e4 key-scale envelope for the
            # f32 oracles.
            _warn_group_key_scale(groups, y, tol=1e-3, stacklevel=4)
        self.n_pairs = _exact_pairs(y, groups)
        if self.n_pairs == 0:
            raise ValueError('training data induces no preference pairs')
        self._y = jnp.asarray(y)
        self._g = None if groups is None else jnp.asarray(groups)
        if loss == 'hinge':
            self.norm, pw = float(self.n_pairs), None
        else:
            # N+/W are zero exactly when n_pairs is, so the gate above
            # already guarantees a positive normalizer here.
            norm, pw = _loss_norm_weights(y, groups, loss)
            self.norm = float(norm)
        self._pw = None if pw is None else jnp.asarray(pw, f32)
        self._inv_n = 1.0 / self.norm
        self._inv_n_dev = jnp.asarray(self._inv_n, f32)
        if engine is not None:
            # an explicit engine override also owns the block: only the
            # O(m^2) blocked engine consumes one.
            self._block = (min(_validate_block(engine_block,
                                               'engine block'), self.m)
                           if engine == 'blocked' else 0)
        # When the transpose-matvec is host-dispatched (CPU CSR), fusing
        # the iteration on device would force the slower scatter path;
        # solver='auto' keeps such oracles on the host driver.
        self.prefer_device_solver = bool(self._feats.device_rmatvec)

    def loss_and_subgrad(self, w):
        feats = self._feats
        loss, out = _fused_step(
            jnp.asarray(w, f32), feats.arrays, self._y, self._g,
            self._inv_n_dev, self._pw, engine=self._engine,
            block=self._block, kind=feats.kind,
            uniform=getattr(feats, '_uniform', False),
            n=self.n, device_rmatvec=feats.device_rmatvec, loss=self.loss)
        if feats.device_rmatvec:
            return loss, out
        cd = np.asarray(out, np.float64)
        return loss, feats.rmatvec_host(cd * self._inv_n)

    def step_fn(self):
        """Traced `w -> (loss, a)` for bmrm's device driver.

        Always finishes the transpose-matvec on device (device_rmatvec
        forced True): inside the fused bundle_step there is no host to hand
        c - d to, so the csr_rmatvec='host' CPU micro-optimization applies
        to the host driver only.
        """
        feats = self._feats
        y, g, inv_n, pw = self._y, self._g, self._inv_n_dev, self._pw
        cfg = dict(engine=self._engine, block=self._block, kind=feats.kind,
                   uniform=getattr(feats, '_uniform', False), n=self.n,
                   device_rmatvec=True, loss=self.loss)
        arrays = feats.arrays

        def fn(w):
            return _fused_step_impl(w, arrays, y, g, inv_n, pw, **cfg)

        return fn

    def step_parts(self):
        """The `step_fn` trace split into (static fn, data pytree) for
        bmrm's SHARED chunk cache: `fn(w, data)` closes over hashable
        config only, the device arrays travel as the `data` argument.
        Two oracles with equal `step_signature()` therefore reuse ONE
        jitted chunk (jax re-traces per data shape, not per instance) —
        the fixed seconds of retrace/compile an incremental refit's
        fresh merged oracle would otherwise pay on every call
        (DESIGN.md §11)."""
        feats = self._feats
        cfg = dict(engine=self._engine, block=self._block, kind=feats.kind,
                   uniform=getattr(feats, '_uniform', False), n=self.n,
                   device_rmatvec=True, loss=self.loss)

        def fn(w, data):
            arrays, y, g, inv_n, pw = data
            return _fused_step_impl(w, arrays, y, g, inv_n, pw, **cfg)

        return fn, (feats.arrays, self._y, self._g, self._inv_n_dev,
                    self._pw)

    def step_signature(self):
        """Hashable key under which `step_parts` traces are
        interchangeable: everything `fn` closes over statically. Data
        shapes are deliberately NOT part of the key — the shared jit
        re-traces per shape on its own."""
        feats = self._feats
        return (type(self).__name__, self._engine, self._block,
                feats.kind, bool(getattr(feats, '_uniform', False)),
                self.n, self._g is None, self.loss)


class TreeOracle(_FusedOracle):
    """The paper's method: merge-sort-tree counts, O(ms + m log^2 m)/iter."""

    name = 'tree'
    _engine = 'tree'


class TopPushOracle(_FusedOracle):
    """The TopPush-style top-rank oracle as a first-class method: each
    anchored example is penalized by its margin against the MAX-scoring
    strictly-lower-utility example in its group (`_toppush_loss_coeffs`,
    DESIGN.md §12 — one sorted pass, no frequency vectors, so the
    counting `engine=` knob is inert and accepted only for interface
    parity). Equivalent to `TreeOracle(..., loss='toppush')` /
    `make_oracle(loss='toppush')`; this class is the explicit spelling."""

    name = 'toppush'
    _engine = 'tree'

    def __init__(self, X, y, groups=None, csr_rmatvec: str = 'auto',
                 engine: str | None = None, engine_block: int = 2048):
        super().__init__(X, y, groups=groups, csr_rmatvec=csr_rmatvec,
                         engine=engine, engine_block=engine_block,
                         loss='toppush')
        # the base __init__ suffixes '/toppush' onto every non-hinge
        # oracle; this class IS the toppush oracle, so drop the echo
        self.name = self.name.replace('/toppush', '', 1)


class PairwiseOracle(_FusedOracle):
    """O(m^2) counting engines: the VMEM-blocked dense pass (PairRSVM
    baseline) or, with dispatch='auto', `kernels.pairwise_rank.counts_auto`
    (tiled Pallas kernel for small m on TPU, merge tree otherwise)."""

    def __init__(self, X, y, groups=None, block: int = 2048,
                 dispatch: str = 'blocked', csr_rmatvec: str = 'auto',
                 engine: str | None = None, loss: str = 'hinge'):
        if dispatch not in ('blocked', 'auto'):
            raise ValueError(f'unknown dispatch {dispatch!r}')
        block = _validate_block(block, 'PairwiseOracle block')
        self._engine = 'blocked' if dispatch == 'blocked' else 'auto'
        self.name = 'pairs' if dispatch == 'blocked' else 'auto'
        super().__init__(X, y, groups=groups, csr_rmatvec=csr_rmatvec,
                         engine=engine, engine_block=block, loss=loss)
        if engine is None:
            self._block = min(block, self.m) if dispatch == 'blocked' else 0


class GroupedOracle(_FusedOracle):
    """Per-query LTR: within-group pairs only, still one linearithmic pass
    via the key-offset trick (counts._group_offsets). `inner` picks the
    counting engine applied to the offset keys."""

    name = 'grouped'

    def __init__(self, X, y, groups, inner: str = 'tree', block: int = 2048,
                 csr_rmatvec: str = 'auto', engine: str | None = None,
                 loss: str = 'hinge'):
        if groups is None:
            raise ValueError('GroupedOracle requires group ids')
        if inner not in ('tree', 'pairs', 'auto'):
            raise ValueError(f'unknown inner oracle {inner!r}')
        block = _validate_block(block, 'GroupedOracle block')
        self._engine = {'tree': 'tree', 'pairs': 'blocked',
                        'auto': 'auto'}[inner]
        self.name = f'grouped/{inner}'
        super().__init__(X, y, groups=groups, csr_rmatvec=csr_rmatvec,
                         engine=engine, engine_block=block, loss=loss)
        if engine is None:
            self._block = min(block, self.m) if inner == 'pairs' else 0


# ------------------------------------------------------- streaming oracle


# Jitted entry of the shared counting core for the streaming host path:
# the full score vector arrives chunk-accumulated from host, one O(m)
# device computation produces loss + coefficients. Engine-parameterized
# (static) so the streaming oracle rides the same counting engines as
# the fused ones — its default 'auto' is the measured tiering: tree
# lowering on CPU (bit-identical to the old hardwired 'tree'), Pallas
# kernels on TPU.
_stream_counts = functools.partial(
    jax.jit, static_argnames=('engine', 'block', 'loss'))(_loss_and_coeffs)

DEFAULT_STREAM_BLOCK = 8192


def _fetch_padded(src, B: int, m: int, n: int, i) -> np.ndarray:
    """Block i of `src` as a dense f32 (B, n) slab, zero-row padded at the
    ragged tail (pad rows score 0 and receive v = 0, so they never
    contribute; the score slice drops them before counting). Module-level
    on purpose: `StreamingOracle.step_fn` closes over (src, B, m, n)
    rather than a bound method, so the bmrm chunk cache's weak keying of
    the oracle keeps working (a captured bound method would pin the
    oracle alive through its own cache entry)."""
    i = int(i)
    lo = i * B
    hi = min(lo + B, m)
    blk = np.asarray(src.block(lo, hi), np.float32)
    if hi - lo < B:
        blk = np.concatenate([blk, np.zeros((B - (hi - lo), n),
                                            np.float32)])
    return blk


def _auto_stream_block(m: int, row_bytes: int, memory_budget) -> int:
    """Rows per block from a GiB budget: reserve the O(m) per-example
    vectors (~6 f32 scalars each: p, y, c, d, c-d, v), spend at most half
    the remainder on the one resident block — the other half stays
    headroom for the counting pass's O(m log m) temporaries. `row_bytes`
    is the source's layout-native per-row cost (dense f32 slab, or
    O(nnz_row) for CSR — `RowBlockSource.row_bytes`)."""
    if memory_budget is None:
        return max(1, min(DEFAULT_STREAM_BLOCK, max(m, 1)))
    budget = float(memory_budget) * 2**30
    overhead = 6 * 4 * m
    if budget <= overhead:
        warnings.warn(
            f'memory_budget={memory_budget:g} GiB cannot even hold the '
            f'mandatory O(m) score/coefficient vectors '
            f'(~{overhead / 2**30:.3g} GiB at m={m}); streaming will run '
            'with 1-row blocks, which is almost certainly not what you '
            'want — raise the budget or pass stream_block explicitly.',
            RuntimeWarning, stacklevel=3)
        return 1
    b = int((budget - overhead) * 0.5 // max(row_bytes, 1))
    return max(1, min(b, max(m, 1)))


class StreamingOracle(RankOracle):
    """Out-of-core oracle: two chunked passes over a `RowBlockSource`.

    The paper's subgradient only needs O(m) scalars resident — the score
    vector and the pair-count coefficients — so features never have to be.
    Each oracle call is:

      pass 1  σ = X w,   accumulated block-wise (one (block, n) slab live)
      counts  ONE global O(m log^2 m) tree / grouped pass on the full
              score vector (`_loss_and_coeffs`, the same counting core the
              fused oracles use)
      pass 2  a = Σ_blocks X_blockᵀ v_block,  v = (c - d) / N

    Peak memory is O(block·n + m) regardless of m — features can live in
    RAM, in CSR, or in an `np.memmap` on disk (`data.rowblocks`), lifting
    the fused oracles' device-memory ceiling on m.

    `prefetch=` (blocks of read-ahead; None/'auto' = double-buffer memmap
    sources, synchronous otherwise — `data.rowblocks.resolve_prefetch`)
    overlaps the next block's disk fetch with the current block's matvec
    on BOTH surfaces below: the host passes iterate prefetched payloads,
    and the traced step's callbacks pull from a wraparound `_ReadAhead`
    (the lookahead of the last block warms block 0 of the next pass).
    Results are bit-identical at any depth — only the fetch timing moves.

    Two evaluation surfaces, same math:
      * `loss_and_subgrad` — host-chunk passes (float64 numpy per-block
        matvecs, layout-native for CSR), counts on device.
      * `step_fn` — the device-driver contract: the SAME two passes as
        `lax.scan` loops whose bodies pull one padded slab from the host
        source via `jax.pure_callback`, so `bmrm(solver='device')` and
        `RankSVM.path()` compose unchanged (one jitted bundle_step,
        sync_every-chunked; the f32 slab is the only feature storage that
        ever exists device-side).
    """

    name = 'stream'
    device_resident = False
    supports_device_solver = True
    prefer_device_solver = True
    supports_path_vmap = False   # pure_callback fetches have no batch rule

    def __init__(self, X, y, groups=None, block_rows: int | None = None,
                 memory_budget: float | None = None,
                 engine: str = 'auto', prefetch=None,
                 loss: str = 'hinge'):
        _validate_loss(loss)
        self.loss = loss
        _counts._validate_engine(engine)
        self._engine = engine
        self._cblock = 2048 if engine == 'blocked' else 0
        y = np.asarray(y, np.float32)
        self._src = _rowblocks.as_row_block_source(X)
        self._prefetch = resolve_prefetch(self._src, prefetch)
        self.m, self.n = self._src.m, self._src.n
        if y.shape[0] != self.m:
            raise ValueError(f'X has {self.m} rows but y has {y.shape[0]}')
        if groups is not None:
            groups = _validate_groups(groups, self.m)   # compact-relabels
            # same ~1e-3 f32 key-scale tolerance as the fused oracles: the
            # streaming counts run on f32 scores through the same core.
            _warn_group_key_scale(groups, y, tol=1e-3, stacklevel=3)
        self.n_pairs = _exact_pairs(y, groups)
        if self.n_pairs == 0:
            raise ValueError('training data induces no preference pairs')
        if block_rows is None:
            # In-flight read-ahead blocks count against the budget: depth
            # pending + 1 being consumed.
            block_rows = _auto_stream_block(
                self.m, self._src.row_bytes() * (1 + self._prefetch),
                memory_budget)
        block_rows = _validate_block(block_rows, 'StreamingOracle '
                                     'block_rows')
        self._B = min(block_rows, self.m)
        self._nblk = self._src.n_blocks(self._B)
        self._y = jnp.asarray(y)
        self._g = None if groups is None else jnp.asarray(groups)
        if loss == 'hinge':
            self.norm, pw = float(self.n_pairs), None
        else:
            norm, pw = _loss_norm_weights(y, groups, loss)
            self.norm = float(norm)
        self._pw = None if pw is None else jnp.asarray(pw, f32)
        self._inv_n = 1.0 / self.norm
        self._inv_n_dev = jnp.asarray(self._inv_n, f32)
        self.name = f'stream/{self._src.kind}'
        if loss != 'hinge':
            self.name = f'{self.name}/{loss}'
        # The traced step densifies one (block, n) slab per fetch; for CSR
        # sources the host-chunk passes instead run layout-native on the
        # sparse row slices (O(nnz_block), no densification), so
        # solver='auto' keeps them on the host driver — the streaming
        # analogue of the fused oracles' csr_rmatvec exception. Dense and
        # memmap sources stream the same bytes either way and take the
        # fused-chunk dispatch win.
        self.prefer_device_solver = self._src.kind != 'csr'

    @property
    def block_rows(self) -> int:
        return self._B

    @property
    def prefetch(self) -> int:
        """Resolved read-ahead depth (0 = synchronous fetches)."""
        return self._prefetch

    def block_resident_bytes(self) -> int:
        """Peak feature bytes resident at any point of a pass, at the
        source's layout-native per-row cost (dense f32 slab; O(nnz_row)
        for CSR, whose solver='auto' path keeps blocks sparse) — the
        O(block) term of the memory model, counting the read-ahead's
        in-flight blocks (`prefetch` pending + 1 consumed); the O(m)
        score/coefficient vectors come on top. Forcing solver='device'
        on a CSR source densifies each slab to block_rows * n * 4 bytes
        instead."""
        return (1 + self._prefetch) * self._B * self._src.row_bytes()

    def loss_and_subgrad(self, w):
        src, B, depth = self._src, self._B, self._prefetch
        w64 = np.asarray(w, np.float64)
        p = np.empty(self.m, np.float32)
        for lo, hi, payload in src.iter_payloads(B, prefetch=depth):
            p[lo:hi] = src._payload_matvec(payload, w64)
        loss, cd = _stream_counts(jnp.asarray(p), self._y, self._g,
                                  self._inv_n_dev, self._pw,
                                  engine=self._engine, block=self._cblock,
                                  loss=self.loss)
        v = np.asarray(cd, np.float64) * self._inv_n
        a = np.zeros(self.n, np.float64)
        for lo, hi, payload in src.iter_payloads(B, prefetch=depth):
            a += src._payload_rmatvec(payload, v[lo:hi])
        return loss, a

    def step_fn(self):
        """Traced `w -> (loss, a)` with the block fetches inside the trace
        (`jax.pure_callback` per scan step), for bmrm's device driver.
        Everything the closure needs is bound to locals — never `self` —
        so the driver's weak-keyed chunk cache can release the oracle
        (same discipline as `_FusedOracle.step_fn`)."""
        B, n, m, nblk = self._B, self.n, self.m, self._nblk
        y, g, inv_n, pw = self._y, self._g, self._inv_n_dev, self._pw
        engine, cblock, loss_name = self._engine, self._cblock, self.loss
        fetch = functools.partial(_fetch_padded, self._src, B, m, n)
        if self._prefetch and nblk > 1:
            # Wraparound read-ahead: while the device multiplies block i,
            # the thread fetches (i+1) % nblk — so the last block of the
            # score pass warms block 0 of the gradient pass, and the last
            # block of an oracle call warms the next call's first fetch.
            # get(i) is exact for ANY callback order (a miss just fetches
            # synchronously), so correctness never leans on scan order.
            fetch = _rowblocks._ReadAhead(fetch, nblk, self._prefetch,
                                          wrap=True).get
        slab = jax.ShapeDtypeStruct((B, n), f32)
        pad = nblk * B - m

        def fn(w):
            def score_blk(carry, i):
                blk = jax.pure_callback(fetch, slab, i)
                return carry, blk @ w

            _, ps = jax.lax.scan(score_blk, jnp.zeros((), f32),
                                 jnp.arange(nblk))
            p = ps.reshape(-1)[:m] if pad else ps.reshape(-1)
            loss, cd = _loss_and_coeffs(p, y, g, inv_n, pw, engine=engine,
                                        block=cblock, loss=loss_name)
            v = cd * inv_n
            vb = (jnp.pad(v, (0, pad)) if pad else v).reshape(nblk, B)

            def grad_blk(acc, xs):
                i, vi = xs
                blk = jax.pure_callback(fetch, slab, i)
                return acc + blk.T @ vi, None

            a, _ = jax.lax.scan(grad_blk, jnp.zeros(n, f32),
                                (jnp.arange(nblk), vb))
            return loss, a

        return fn


# --------------------------------------------------------- sharded oracle


def _default_mesh() -> Mesh:
    """All local devices on the 'data' axis (counts/query parallel), model
    axis 1 — the degenerate single-host version of launch.mesh."""
    dev = np.array(jax.devices())
    return Mesh(dev.reshape(dev.size, 1), ('data', 'model'))


class ShardedOracle(RankOracle):
    """Pod-scale oracle: wraps `core.distributed.make_oracle_body` (2-D
    sharded bf16 X, all-gathered scores, query-sharded tree — DESIGN.md §5)
    behind the same interface, so `RankSVM(method='sharded')` and the
    dry-run tooling exercise one code path. Group ids are accepted like any
    other oracle: they shard row-wise with y, and the counting phase folds
    them in via the key-offset trick — per-query LTR at pod scale.

    A first-class citizen of the device bundle driver: `step_fn` is the
    traced mesh step (same contract as `_FusedOracle.step_fn`), and
    `state_shardings` hands bmrm the `BundleState` annotations (replicated
    QP state, plane buffer column-sharded over 'model') so the whole fused
    `bundle_step` runs under the mesh without per-step resharding.

    Note the matvecs run in bf16 (the deliberate pod-scale trade); the
    counts see bf16-rounded scores, so parity with the f32 oracles is
    approximate (~1e-2), which BMRM tolerates as an inexact oracle.

    Three feature layouts, one oracle (DESIGN.md §9):
      * dense ndarray — 2-D sharded bf16, einsum matvecs (the original
        path).
      * CSR (`repro.data.sparse.CSRMatrix`, scipy sparse, or a
        `CSRBlockSource`) — stays SPARSE: rows padded to the max nnz/row
        slot count (`core.distributed.csr_slot_arrays`), both slot
        arrays row-sharded, segment-sum matvecs at O(nnz) cost
        (`make_csr_oracle_body`). No densification, no projected-GiB
        trap; 6 bytes/slot vs 2 bytes/dense-column, a win below ~n/3
        nonzeros per row.
      * `np.memmap` / any other `RowBlockSource` — streamed per-host
        assembly (`core.distributed.assemble_row_sharded`): each host
        reads only its own devices' row ranges, `prefetch` blocks ahead
        (`block_rows` per read), so X is never host-resident and the
        fully-X-in-RAM requirement of the sharded path is lifted.
    """

    name = 'sharded'
    device_resident = True
    supports_device_solver = True
    prefer_device_solver = True
    supports_path_vmap = True    # traced mesh body; vmap inserts a leading
    # replicated lambda axis into its sharding constraints

    def __init__(self, X, y, groups=None, mesh: Mesh | None = None,
                 variant: str = 'base', engine: str = 'tree',
                 block_rows: int | None = None, prefetch=None,
                 loss: str = 'hinge'):
        # loss gate FIRST: an unsupported loss must fail before any
        # densify, padding, or device transfer below touches X.
        _validate_loss(loss)
        _dist.validate_sharded_loss(loss)
        if mesh is not None:
            _dist.validate_mesh_axes(mesh)
        self.loss = loss
        _counts._validate_engine(engine)
        _validate_prefetch(prefetch)
        y = np.asarray(y, np.float32)
        src = None
        if isinstance(X, (np.memmap, _rowblocks.RowBlockSource)) and \
                not isinstance(X, _rowblocks.CSRBlockSource):
            src = _rowblocks.as_row_block_source(X)
            layout = 'stream'
            self.m, self.n = src.m, src.n
        else:
            if isinstance(X, _rowblocks.CSRBlockSource):
                X = X._X                     # the layout-native CSR object
            if _scipy_sparse is not None and _scipy_sparse.issparse(X):
                X = X.tocsr()
            if _is_csr_like(X):
                layout = 'csr'
            else:
                layout = 'dense'
                X = np.asarray(X)
                if X.ndim != 2:
                    raise ValueError('ShardedOracle features must be 2-D; '
                                     f'got shape {X.shape}')
            self.m, self.n = map(int, X.shape)
        if y.shape[0] != self.m:
            raise ValueError(f'X has {self.m} rows but y has {y.shape[0]}')
        if groups is not None:
            groups = _validate_groups(groups, self.m)   # compact-relabels
            # ~1e-2 tolerance: the bf16 matvecs already round the scores.
            _warn_group_key_scale(groups, y, tol=1e-2, stacklevel=3)
        self.n_pairs = _exact_pairs(y, groups)
        if self.n_pairs == 0:
            raise ValueError('training data induces no preference pairs')
        self.norm = float(self.n_pairs)   # hinge-only (the gate above)
        self._mesh = mesh if mesh is not None else _default_mesh()
        rows = [a for a in ('pod', 'data') if a in self._mesh.axis_names]
        rsize = int(np.prod([self._mesh.shape[a] for a in rows]))
        msize = int(self._mesh.shape.get('model', 1))
        if self.n % msize:
            raise ValueError(
                f"mesh 'model' axis of size {msize} does not divide the "
                f'feature dim n={self.n}; pick a mesh whose model axis '
                'divides n (or pad the features upstream)')
        # Row padding to the mesh row multiple: padded rows are all-zero
        # features in their OWN group with tied y, so they induce no pairs,
        # zero counts, and zero loss/subgradient contribution — results are
        # exactly those of the unpadded problem.
        pad = (-self.m) % rsize
        if pad:
            y = np.concatenate([y, np.zeros(pad, np.float32)])
            base = groups if groups is not None else np.zeros(self.m,
                                                              np.int32)
            pad_id = int(base.max()) + 1 if self.m else 0
            groups = np.concatenate([base,
                                     np.full(pad, pad_id, np.int32)])
        sh = _dist.arg_shardings(self._mesh)
        if layout == 'csr':
            self.name = 'sharded/csr'
            data2, idx2 = _dist.csr_slot_arrays(
                X.data, X.indices, X.indptr, (self.m, self.n),
                pad_rows=pad)
            self._body = _dist.make_csr_oracle_body(
                self._mesh, variant=variant, engine=engine)
            self._args = (
                jax.device_put(jnp.asarray(data2, jnp.bfloat16),
                               sh['data2']),
                jax.device_put(jnp.asarray(idx2), sh['idx2']))
        elif layout == 'stream':
            self.name = 'sharded/stream'
            block = _validate_block(
                block_rows if block_rows is not None
                else DEFAULT_STREAM_BLOCK, 'ShardedOracle block_rows')
            self._body = _dist.make_oracle_body(self._mesh, variant=variant,
                                                engine=engine)
            self._args = (_dist.assemble_row_sharded(
                src, sh['X'], (self.m + pad, self.n),
                block_rows=min(block, max(self.m, 1)), prefetch=prefetch),)
        else:
            self.name = 'sharded'
            if pad:
                X = np.concatenate([X, np.zeros((pad, self.n), X.dtype)])
            self._body = _dist.make_oracle_body(self._mesh, variant=variant,
                                                engine=engine)
            self._args = (jax.device_put(jnp.asarray(X, jnp.bfloat16),
                                         sh['X']),)
        self._fn = jax.jit(self._body)
        self._yd = jax.device_put(jnp.asarray(y, f32), sh['y'])
        self._g = (None if groups is None
                   else jax.device_put(jnp.asarray(groups), sh['g']))
        self._np = jax.device_put(jnp.asarray(float(self.n_pairs), f32),
                                  sh['n_pairs'])
        self._wsh = sh['w']

    def loss_and_subgrad(self, w):
        wd = jax.device_put(jnp.asarray(np.asarray(w), f32), self._wsh)
        return self._fn(*self._args, self._yd, self._g, wd, self._np)

    def step_fn(self):
        """Traced `w -> (loss, a)` over the mesh-sharded arrays, for bmrm's
        device driver (the sharded analogue of `_FusedOracle.step_fn`)."""
        args, y, g, n_pairs = self._args, self._yd, self._g, self._np
        body = self._body

        def fn(w):
            return body(*args, y, g, w, n_pairs)

        return fn

    def state_shardings(self, batched: bool = False):
        """BundleState annotations for bmrm's device driver on this mesh
        (`batched=True`: the (n_lams, ...)-leading layout of the vmapped
        path sweep — see `core.bmrm.bundle_state_shardings`)."""
        from .bmrm import bundle_state_shardings
        return bundle_state_shardings(self._mesh, batched=batched)


def sharded_dryrun_cell(mesh: Mesh, shape=None, variant: str = 'base',
                        kind: str = 'bundle', max_planes: int = 64,
                        qp_iters: int = 128, grouped: bool = True):
    """(jitted fn, abstract args) for compile-only dry runs of the sharded
    path — the launch.dryrun entry point into this layer.

    kind='bundle' (default) lowers the FULL device-driver iteration: one
    `core.bmrm._bundle_step` with the mesh oracle inlined — fused oracle
    step, plane insert into the column-sharded buffer, incremental Gram,
    and the on-device masked FISTA QP — under `bundle_state_shardings`.
    By default the GROUPED program is lowered (`grouped=False` for the
    ungrouped variant): per-query LTR is the production pod path, and the
    grouped program is a strict superset (all-gathered int32 g + the
    key-offset math), so it is the one compile-only verification must
    cover. kind='oracle' lowers just the ungrouped (loss, subgradient)
    evaluation (the pre-PR-3 cell, kept for A/B roofline comparisons).
    """
    from .bmrm import (_bundle_step, abstract_bundle_state,
                       bundle_state_shardings)
    from jax.sharding import NamedSharding, PartitionSpec
    shape = shape if shape is not None else _dist.REUTERS_1M
    specs = _dist.input_specs(None, shape)
    sh = _dist.arg_shardings(mesh)
    if kind == 'oracle':
        fn = jax.jit(_dist.make_oracle_step(mesh, variant=variant),
                     in_shardings=(sh['X'], sh['y'], sh['w'], sh['n_pairs']),
                     out_shardings=_dist.out_shardings(mesh))
        return fn, (specs['X'], specs['y'], specs['w'], specs['n_pairs'])
    if kind != 'bundle':
        raise ValueError(f'unknown dry-run kind {kind!r}')
    body = _dist.make_oracle_body(mesh, variant=variant)

    ssh = bundle_state_shardings(mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    scalar = jax.ShapeDtypeStruct((), f32)
    state_spec = abstract_bundle_state(shape.n, max_planes)
    if grouped:
        def step(state, X, y, g, n_pairs, lam, eps):
            return _bundle_step(state, lambda w: body(X, y, g, w, n_pairs),
                                lam, eps, qp_iters)

        fn = jax.jit(step,
                     in_shardings=(ssh, sh['X'], sh['y'], sh['g'],
                                   sh['n_pairs'], rep, rep),
                     out_shardings=(ssh, rep))
        return fn, (state_spec, specs['X'], specs['y'], specs['g'],
                    specs['n_pairs'], scalar, scalar)

    def step(state, X, y, n_pairs, lam, eps):
        return _bundle_step(state, lambda w: body(X, y, None, w, n_pairs),
                            lam, eps, qp_iters)

    fn = jax.jit(step,
                 in_shardings=(ssh, sh['X'], sh['y'], sh['n_pairs'],
                               rep, rep),
                 out_shardings=(ssh, rep))
    return fn, (state_spec, specs['X'], specs['y'], specs['n_pairs'],
                scalar, scalar)


# ---------------------------------------------------------------- factory


METHODS = ('tree', 'pairs', 'auto', 'sharded', 'stream')


def make_oracle(X, y, groups=None, method: str = 'tree', *,
                loss: str = 'hinge', engine: str | None = None,
                pair_block: int = 2048, mesh: Mesh | None = None,
                variant: str = 'base', csr_rmatvec: str = 'auto',
                memory_budget: float | None = None,
                stream_block: int | None = None,
                prefetch=None) -> RankOracle:
    """Build the RankOracle for (X, y[, groups]) selected by `method`.

    Dispatch table (features-resident column is the memory model;
    `groups=` routes the first three through GroupedOracle with the same
    engine, and works natively on 'sharded' and 'stream'. The path-sweep
    column says what `RankSVM.path(mode='auto')` / `bmrm_path` resolves
    to for that oracle — 'vmap' batches the whole lambda grid into one
    device program, 'sequential' warm-starts fit-by-fit; see
    `supports_path_vmap` and DESIGN.md §7):

      method     oracle            features resident        counts engine
                                                            | path mode
      'tree'     TreeOracle        full X on device (f32)   merge-sort tree
                                                            | vmap
      'pairs'    PairwiseOracle    full X on device (f32)   blocked O(m^2)
                                                            | vmap
      'auto'     PairwiseOracle    full X on device (f32)   counts_auto
                 or StreamingOracle — see budget rule below  | per oracle
      'sharded'  ShardedOracle     X sharded over mesh      tree on the
                                   (bf16, dense)            gathered scores
                                                            | vmap
      'stream'   StreamingOracle   ONE (block, n) f32 slab  ONE global
                                   + O(m) vectors           engine pass
                                                            (default 'auto')
                                                            | sequential
                                                            (pure_callback
                                                            cannot vmap)

    (Two measured path-mode exceptions: CPU CSR inputs' fused oracles set
    prefer_device_solver=False — host bincount beats XLA scatter there —
    so path mode='auto' keeps them on the sequential host sweep; and on
    the serial CPU backend mode='auto' runs EVERY oracle sequentially,
    since the batched sweep measures 2-8x slower there — EXPERIMENTS
    §Path sweep. 'vmap' in the column means "batches under mode='auto'
    on accelerator backends, and under an explicit mode='vmap'
    anywhere".)

    method='auto' resolves fused-vs-streaming by projected resident
    memory (`data.rowblocks.projected_resident_gib` — what a fused oracle
    would pin for this X): it streams when that projection exceeds
    `memory_budget` GiB, and always when X is an `np.memmap` or a
    `RowBlockSource` (layouts with no sensible fused form); otherwise it
    keeps the fused counts_auto oracle. With no budget and in-memory X
    the dispatch is unchanged from before. method='stream' forces the
    streaming oracle for any X. method='sharded' accepts every layout:
    CSR input stays sparse (the padded-slot segment-sum body — no
    densification), and memmap/`RowBlockSource` input is assembled shard
    by shard per host (`core.distributed.assemble_row_sharded`) without
    ever materializing X.

    `stream_block` (rows per block) defaults to a budget-derived size
    (`_auto_stream_block`: the block gets at most half the budget left
    after the O(m) vectors — counting the read-ahead's in-flight blocks —
    at the source's layout-native per-row cost: dense f32 slab, or
    O(nnz_row) for CSR); `pair_block` is the VMEM/cache block of the
    O(m^2) engine. Both are validated as positive whole row counts. It
    also sizes the per-host assembly reads of the streamed sharded path.

    `prefetch` (None/'auto' | int >= 0) is the row-block read-ahead
    depth for the streaming oracle's passes and the sharded oracle's
    per-host assembly: a background thread fetches up to that many
    blocks ahead of the consumer (`data.rowblocks._ReadAhead`),
    overlapping disk latency with compute. The auto rule double-buffers
    memmap sources and stays synchronous for in-RAM layouts
    (`data.rowblocks.resolve_prefetch`); results are bit-identical at
    any depth. Ignored by the fused oracles (nothing is streamed).

    `engine=` overrides the COUNTING ENGINE of whatever oracle `method`
    selects (orthogonal to the method's memory model / residency
    choice), validated up front against `counts.ENGINES`:

      engine     counting pass (`counts.counts_dispatch`)
      None       the method's own default (table above)
      'tree'     merge-sort tree, one fused pass (`counts_fused`)
      'blocked'  O(m^2) pairwise, `pair_block`-row VMEM blocks
      'pallas'   fused rank-counts Pallas kernel: both frequency
                 vectors in one tiled on-chip pass (DESIGN.md §8;
                 interpret-mode off TPU, vmap-safe for path sweeps)
      'auto'     measured tiering (`kernels.pairwise_rank.counts_auto`):
                 TPU = pairwise kernel to 4096 elements then
                 rank-counts kernel; elsewhere tree lowering —
                 EXPERIMENTS.md §Counts kernel

    The streaming oracle's one global counting pass defaults to 'auto'
    (identical to its previous hardwired tree on CPU, kernel pickup on
    accelerators); the sharded oracle defaults to 'tree' (the only
    engine with a partitioned counting path — any other engine counts
    on the all-gathered replicated scores, matvecs still sharded).
    """
    if method not in METHODS:
        raise ValueError(f'unknown oracle method {method!r}; '
                         f'expected one of {METHODS}')
    _validate_loss(loss)
    if method == 'sharded':
        # reject BEFORE construction: ShardedOracle.__init__ would densify
        # / pad / device_put X, and an unsupported loss must never get
        # that far (the acceptance contract of DESIGN.md §12).
        _dist.validate_sharded_loss(loss)
    if engine is not None:
        _counts._validate_engine(engine)
    _validate_prefetch(prefetch)
    stream_only = isinstance(X, (_rowblocks.RowBlockSource, np.memmap))
    if method == 'auto' and not stream_only and memory_budget is not None:
        if _rowblocks.projected_resident_gib(X) > float(memory_budget):
            method = 'stream'
    if method == 'stream' or (method == 'auto' and stream_only):
        return StreamingOracle(X, y, groups=groups, block_rows=stream_block,
                               memory_budget=memory_budget,
                               engine=engine if engine is not None
                               else 'auto', prefetch=prefetch, loss=loss)
    if method == 'sharded':
        return ShardedOracle(X, y, groups=groups, mesh=mesh, variant=variant,
                             engine=engine if engine is not None else 'tree',
                             block_rows=stream_block, prefetch=prefetch,
                             loss=loss)
    if isinstance(X, _rowblocks.RowBlockSource):
        raise ValueError(
            f"method={method!r} needs materialized features, but X is a "
            f'{type(X).__name__} row-block source; train it with '
            "method='stream' or 'sharded' (or 'auto', which streams "
            'such sources)')
    if groups is not None:
        return GroupedOracle(X, y, groups, inner=method, block=pair_block,
                             csr_rmatvec=csr_rmatvec, engine=engine,
                             loss=loss)
    if method == 'tree':
        return TreeOracle(X, y, csr_rmatvec=csr_rmatvec, engine=engine,
                          engine_block=pair_block, loss=loss)
    return PairwiseOracle(
        X, y, block=pair_block,
        dispatch='auto' if method == 'auto' else 'blocked',
        csr_rmatvec=csr_rmatvec, engine=engine, loss=loss)


def empirical_risk(scores, utilities, groups=None, loss: str = 'hinge'):
    """R_emp for precomputed scores — the loss-generic evaluation helper.

    The same normalized risk the training oracles minimize ('hinge' = the
    mean pairwise hinge over N preference pairs; 'toppush' = the mean
    anchored top-rank margin over N+; 'poshinge' = the position-weighted
    pair hinge over weight mass W), evaluated from a score vector instead
    of (X, w) — what `RankSVM.objective` and the differential tests use.
    Returns a host float; 0.0 when the data induces no preference pairs
    (all three normalizers vanish together, see `_loss_norm_weights`).
    """
    _validate_loss(loss)
    y = np.asarray(utilities, np.float32)
    if groups is not None:
        groups = _validate_groups(groups, y.shape[0])
    norm, pw = _loss_norm_weights(y, groups, loss)
    if norm == 0:
        return 0.0
    p = jnp.asarray(np.asarray(scores, np.float32))
    g = None if groups is None else jnp.asarray(groups)
    val, _ = _stream_counts(
        p, jnp.asarray(y), g, jnp.asarray(1.0 / float(norm), f32),
        None if pw is None else jnp.asarray(pw, f32),
        engine='tree', block=0, loss=loss)
    return float(val)
