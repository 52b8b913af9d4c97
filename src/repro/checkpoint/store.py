"""Sharded, restart-safe checkpoint store (msgpack + zstd, no orbax offline).

Layout (one directory per step):

    <root>/step_00000042/
        meta.json                 # step, tree structure, shard map, mesh info
        shard_00000_of_00004.bin  # zstd(msgpack list of leaf chunk bytes)
        COMMITTED                 # written LAST -> atomic-visibility marker

Design points for the 1000+ node target:
  * Each host writes only the leaf-shards it owns (`shard_filter`); a single
    process writes everything. Restore reads only what the local mesh needs.
  * The COMMITTED marker makes partially-written checkpoints invisible;
    `latest_step` skips uncommitted dirs, so a crash mid-save is harmless
    (classic two-phase commit, same contract as orbax).
  * Elastic restore: leaves are stored UNSHARDED per leaf-chunk (row-chunked
    for large arrays), so a restart on a different mesh/dp-size just re-shards
    on load — checkpoint layout is mesh-independent.
  * `keep` garbage collection bounds disk usage.
"""

from __future__ import annotations

import json
import os
import shutil

import jax
import jax.numpy as jnp
import msgpack
import numpy as np

try:
    import zstandard
except ImportError:            # optional: only needed for compression='zstd'
    zstandard = None

_CHUNK = 1 << 26               # 64 MiB raw chunks inside a shard file
_LEVEL = 3
_ZSTD_MAGIC = b'\x28\xb5\x2f\xfd'   # zstd frame header


def _require_zstandard(what: str):
    if zstandard is None:
        raise ModuleNotFoundError(
            f'{what} requires the optional `zstandard` package '
            f"(pip install zstandard, or the project's [compression] "
            f"extra); pass compression='none' to save uncompressed.")
    return zstandard


def _tree_flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ['/'.join(str(getattr(k, 'key', getattr(k, 'idx', k)))
                      for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def _step_dir(root: str, step: int) -> str:
    return os.path.join(root, f'step_{step:08d}')


def save(root: str, step: int, tree, *, n_shards: int = 1,
         shard_filter=None, compression: str = 'auto',
         meta_extra: dict | None = None) -> str:
    """Write `tree` (pytree of arrays) as checkpoint `step` under `root`.

    Args:
      n_shards: number of shard files (hosts) the leaves are striped over.
      shard_filter: optional callable shard_id -> bool; a host writes only
        shards for which this returns True (multi-host mode). The COMMITTED
        marker must then be written by exactly one designated host after a
        barrier — `commit()` below, host 0 in `runtime.train_loop`.
      compression: 'zstd' | 'none' | 'auto' ('zstd' when the optional
        zstandard package is installed, else 'none'). 'zstd' without the
        package raises a clear ModuleNotFoundError.
      meta_extra: optional dict of JSON-serializable entries merged into
        meta.json (e.g. {'loss': 'toppush'} so a resumed training run
        re-validates its objective against the checkpoint's — `restore`
        hands the merged meta back). Keys used by the store itself
        ('step', 'n_shards', 'compression', 'leaves') are reserved and
        rejected rather than silently clobbered.
    Returns the checkpoint directory.
    """
    if meta_extra:
        clash = {'step', 'n_shards', 'compression',
                 'leaves'} & set(meta_extra)
        if clash:
            raise ValueError(f'meta_extra may not override reserved meta '
                             f'keys {sorted(clash)}')
    if compression == 'auto':
        compression = 'zstd' if zstandard is not None else 'none'
    if compression not in ('zstd', 'none'):
        raise ValueError(f'unknown compression {compression!r}')
    cctx = (_require_zstandard("compression='zstd'")
            .ZstdCompressor(level=_LEVEL) if compression == 'zstd' else None)

    d = _step_dir(root, step)
    os.makedirs(d, exist_ok=True)
    paths, leaves, _ = _tree_flatten_with_paths(tree)

    arrays = [np.asarray(jax.device_get(x)) for x in leaves]
    meta = {'step': int(step), 'n_shards': int(n_shards),
            'compression': compression, 'leaves': []}
    if meta_extra:
        meta.update(meta_extra)

    shards = [[] for _ in range(n_shards)]   # per-shard list of chunk records
    for li, (p, a) in enumerate(zip(paths, arrays)):
        dt = a.dtype
        store_dt = np.uint16 if dt == jnp.bfloat16 else dt
        raw = a.view(store_dt) if dt == jnp.bfloat16 else a
        buf = raw.tobytes()
        chunks = [buf[o:o + _CHUNK] for o in range(0, max(len(buf), 1),
                                                   _CHUNK)]
        recs = []
        for ci, ch in enumerate(chunks):
            sid = (li + ci) % n_shards
            recs.append({'shard': sid, 'index': len(shards[sid])})
            shards[sid].append(ch)
        meta['leaves'].append({
            'path': p, 'shape': list(a.shape), 'dtype': str(dt),
            'chunks': recs, 'nbytes': len(buf)})

    for sid in range(n_shards):
        if shard_filter is not None and not shard_filter(sid):
            continue
        fn = os.path.join(d, f'shard_{sid:05d}_of_{n_shards:05d}.bin')
        payload = msgpack.packb(shards[sid], use_bin_type=True)
        if cctx is not None:
            payload = cctx.compress(payload)
        with open(fn + '.tmp', 'wb') as f:
            f.write(payload)
        os.replace(fn + '.tmp', fn)

    with open(os.path.join(d, 'meta.json.tmp'), 'w') as f:
        json.dump(meta, f)
    os.replace(os.path.join(d, 'meta.json.tmp'), os.path.join(d, 'meta.json'))
    if shard_filter is None:
        commit(root, step)
    return d


def commit(root: str, step: int) -> None:
    """Write the atomic-visibility marker (call once, after all hosts saved)."""
    marker = os.path.join(_step_dir(root, step), 'COMMITTED')
    with open(marker, 'w') as f:
        f.write('ok')


def latest_step(root: str) -> int | None:
    """Largest committed step under root, or None."""
    if not os.path.isdir(root):
        return None
    best = None
    for name in os.listdir(root):
        if not name.startswith('step_'):
            continue
        if not os.path.exists(os.path.join(root, name, 'COMMITTED')):
            continue
        s = int(name.split('_')[1])
        best = s if best is None or s > best else best
    return best


def restore(root: str, step: int | None = None, *, like=None,
            shardings=None):
    """Load checkpoint `step` (default latest). If `like` (a pytree of arrays
    or ShapeDtypeStructs) is given, the stored leaves are mapped onto its
    structure; `shardings` (matching pytree of NamedSharding) re-shards each
    leaf for the *current* mesh — this is the elastic-restart path."""
    if step is None:
        step = latest_step(root)
        if step is None:
            raise FileNotFoundError(f'no committed checkpoint under {root}')
    d = _step_dir(root, step)
    with open(os.path.join(d, 'meta.json')) as f:
        meta = json.load(f)
    shard_cache: dict[int, list] = {}

    def shard(sid: int):
        if sid not in shard_cache:
            fn = os.path.join(
                d, f'shard_{sid:05d}_of_{meta["n_shards"]:05d}.bin')
            with open(fn, 'rb') as f:
                payload = f.read()
            # Detect compression PER SHARD by the zstd frame magic rather
            # than trusting meta['compression']: with compression='auto'
            # and shard_filter, hosts with and without zstandard installed
            # can legitimately mix shard formats under one checkpoint (and
            # meta.json is last-writer-wins across hosts).
            if payload[:4] == _ZSTD_MAGIC:
                dctx = _require_zstandard(
                    'restoring a zstd-compressed shard').ZstdDecompressor()
                payload = dctx.decompress(payload)
            shard_cache[sid] = msgpack.unpackb(payload, raw=False)
        return shard_cache[sid]

    leaves = {}
    for rec in meta['leaves']:
        buf = b''.join(shard(c['shard'])[c['index']] for c in rec['chunks'])
        dt = rec['dtype']
        if dt == 'bfloat16':
            a = np.frombuffer(buf, np.uint16).copy().view(jnp.bfloat16)
        else:
            a = np.frombuffer(buf, np.dtype(dt)).copy()
        leaves[rec['path']] = a.reshape(rec['shape'])

    if like is None:
        return leaves, meta

    paths, like_leaves, treedef = _tree_flatten_with_paths(like)
    out = []
    for p, ll in zip(paths, like_leaves):
        if p not in leaves:
            raise KeyError(f'checkpoint missing leaf {p!r}')
        a = leaves[p]
        want_shape = tuple(ll.shape)
        if tuple(a.shape) != want_shape:
            raise ValueError(f'leaf {p}: ckpt {a.shape} != model {want_shape}')
        out.append(a)
    tree = jax.tree.unflatten(treedef, out)
    if shardings is not None:
        tree = jax.tree.map(
            lambda a, s: jax.device_put(a, s) if s is not None
            else jnp.asarray(a), tree, shardings)
    else:
        tree = jax.tree.map(jnp.asarray, tree)
    return tree, meta


def gc(root: str, keep: int) -> list:
    """Delete all but the newest `keep` committed checkpoints (+ any
    uncommitted debris older than the newest committed one)."""
    if not os.path.isdir(root):
        return []
    steps = sorted(
        int(n.split('_')[1]) for n in os.listdir(root)
        if n.startswith('step_')
        and os.path.exists(os.path.join(root, n, 'COMMITTED')))
    drop = steps[:-keep] if keep > 0 else []
    removed = []
    for s in drop:
        shutil.rmtree(_step_dir(root, s), ignore_errors=True)
        removed.append(s)
    return removed
