"""Int8 error-feedback gradient compression for data-parallel all-reduce.

At 1000+ node scale the DP gradient all-reduce is the dominant inter-pod
collective; compressing the wire format 4x (f32 -> int8) directly shrinks the
collective roofline term. Scheme (standard error-feedback compression, cf.
1-bit SGD / EF-SGD):

  1. add the carried error-feedback residual to the local gradient,
  2. reduce-scatter in int8: split into |axis| chunks, quantize each chunk
     with a per-chunk f32 scale (max-abs / 127), `all_to_all` the int8
     payload (+ tiny scale vector), dequantize + sum the received chunks ->
     each device owns one exactly-reduced f32 shard,
  3. all-gather the reduced shard, again int8-quantized,
  4. keep residual = local_grad - dequant(sent) for the next step
     (error feedback makes the quantization bias vanish over steps).

Wire bytes per element: ~1 (a2a) + ~1 (ag) vs 4 + 4 for an f32 ring
all-reduce -> ~4x less ICI traffic, at the cost of one extra quantization
round-trip of numerical noise that error feedback absorbs.

`compressed_mean(stacked_tree, mesh, axis)` runs under shard_map on `axis`;
replica i's local summand is row i of each leaf; rows leave as the
(exact-ish) mean. Residual state is returned for the next call. Validated
against the exact mean on a real 8-device mesh in tests/test_compression.py.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

f32 = jnp.float32


def _quant(x):
    """int8 symmetric quantization with f32 scale. x: (..., n)."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q, scale):
    return q.astype(f32) * scale


def _ef_allreduce_flat(g, err, axis_name: str, ndev: int):
    """Error-feedback compressed mean over `axis_name` for (n,) f32 g."""
    n = g.shape[0]
    pad = (-n) % (ndev * 128)              # lane-align the chunks
    gp = jnp.pad(g + err[:n], (0, pad))
    chunks = gp.reshape(ndev, -1)          # (ndev, c)

    q, scale = _quant(chunks)              # (ndev, c) int8, (ndev, 1)
    # reduce-scatter: all_to_all the chunk axis; device d receives chunk d
    # of every peer.
    qx = jax.lax.all_to_all(q[:, None, :], axis_name, split_axis=0,
                            concat_axis=0)            # (ndev, 1, c)
    sx = jax.lax.all_to_all(scale[:, None, :], axis_name, split_axis=0,
                            concat_axis=0)
    shard = jnp.sum(_dequant(qx[:, 0, :], sx[:, 0, :]), axis=0) / ndev

    # all-gather the reduced shard, int8 again
    q2, s2 = _quant(shard[None, :])
    qg = jax.lax.all_gather(q2[0], axis_name)          # (ndev, c)
    sg = jax.lax.all_gather(s2[0], axis_name)
    full = _dequant(qg, sg).reshape(-1)[:n]

    # error feedback: what we failed to transmit of OUR contribution
    sent = _dequant(q, scale).reshape(-1)[:n]
    new_err = (g + err[:n]) - sent
    return full, new_err


def compressed_mean(stacked_tree, mesh, axis: str = 'data',
                    err_tree=None):
    """Compressed mean over mesh axis `axis` with error feedback.

    Args:
      stacked_tree: pytree of (ndev, ...) f32 arrays — leaf[i] is replica
        i's local gradient summand; the leading axis is sharded over `axis`
        (this is how per-device summands are expressed from OUTSIDE a
        manual region; inside a shard_map'd train step you would call
        `_ef_allreduce_flat` directly on the local values).
      err_tree: residual state from the previous call — pytree of (ndev, n)
        f32 leaves (or None). Sharded like the gradients.
    Returns (mean_tree (ndev-less shapes are kept stacked: every replica row
    holds the same mean), new_err_tree).
    """
    ndev = mesh.shape[axis]
    leaves, treedef = jax.tree.flatten(stacked_tree)
    assert all(l.shape[0] == ndev for l in leaves), 'leading dim must = ndev'
    if err_tree is None:
        errs = [jnp.zeros((ndev, l[0].size), f32) for l in leaves]
    else:
        errs = jax.tree.leaves(err_tree)

    def body(*args):
        k = len(args) // 2
        gs, es = args[:k], args[k:]          # each (1, ...) local rows
        outs, nerrs = [], []
        for g, e in zip(gs, es):
            flat = g[0].astype(f32).reshape(-1)
            out, ne = _ef_allreduce_flat(flat, e[0], axis, ndev)
            outs.append(out.reshape((1,) + g.shape[1:]).astype(g.dtype))
            nerrs.append(ne[None])
        return tuple(outs) + tuple(nerrs)

    spec = P(axis)                           # leading replica dim sharded
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(spec for _ in range(2 * len(leaves))),
        out_specs=tuple(spec for _ in range(2 * len(leaves))),
        check_vma=False)
    res = fn(*leaves, *errs)
    outs = list(res[:len(leaves)])
    nerrs = list(res[len(leaves):])
    return jax.tree.unflatten(treedef, outs), jax.tree.unflatten(
        treedef, nerrs)
