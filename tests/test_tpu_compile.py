"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed even where no TPU is: a `v5e:2x2` topology
description stands in for the chip, and `jit(...).lower(...).compile()`
raises what the chip's compiler would raise — misaligned tiles, layouts
Mosaic cannot lower, VMEM or SMEM overflows. The counting kernels run in
interpret mode everywhere else in the suite, so these are the only tests
that see the compiled (`interpret=False`) kernels.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers all import
this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.ranksvm_paper import config
from repro.core import oracle as O
from repro.kernels.pairwise_rank import ops as pw_ops
from repro.kernels.rank_counts import kernel as rc_kernel
from repro.kernels.rank_counts import ops as rc_ops


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert 'tpu_custom_call' in compiled.as_text()


def test_rank_counts_kernel_compiles_at_paper_m(one_chip):
    """The fused rank-counts kernel at m = 2^20 with the default tiles and
    level capacity, as `rank_counts` calls it."""
    m, ti, tj = 1 << 20, 8, 8
    rows = m // rc_kernel.LANES
    args = (_spec(one_chip, (4 * rows // ti,), jnp.int32),
            _spec(one_chip, (rows, rc_kernel.LANES), jnp.float32),
            _spec(one_chip, (rows, rc_kernel.LANES), jnp.int32),
            _spec(one_chip, (rows // tj + 1, rc_ops.DEFAULT_LEVELS),
                  jnp.int32))
    fn = functools.partial(rc_kernel.rank_counts_kernel, ti_rows=ti,
                           tj_rows=tj, interpret=False)
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_pairwise_kernel_compiles_at_tier_limit(one_chip):
    """The pairwise kernel at the largest m `counts_auto` gives it."""
    x = _spec(one_chip, (pw_ops.KERNEL_MAX_M,), jnp.float32)
    fn = functools.partial(pw_ops.pairwise_counts, interpret=False)
    _assert_kernel(jax.jit(fn).lower(x, x).compile())


def test_fused_tree_step_compiles_at_paper_width(one_chip):
    """The jitted fused oracle step of the tree method over uniform-nnz CSR
    rows (the `reuters_like` layout) at the ranksvm-linear width n, with
    the device transpose-matvec; m is cut to 4096 rows to keep the
    compile short."""
    m, s, n = 4096, 50, config().n_features
    arrays = {'data2': _spec(one_chip, (m, s), jnp.float32),
              'idx2': _spec(one_chip, (m, s), jnp.int32)}
    step = functools.partial(
        O._fused_step_impl, engine='tree', block=0, kind='csr',
        uniform=True, n=n, device_rmatvec=True, loss='hinge')
    compiled = jax.jit(step).lower(
        _spec(one_chip, (n,), jnp.float32), arrays,
        _spec(one_chip, (m,), jnp.float32), None,
        _spec(one_chip, (), jnp.float32)).compile()
    loss, a = compiled.out_info
    assert loss.shape == () and a.shape == (n,)
