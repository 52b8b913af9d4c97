"""Unit tests for the logical-axis sharding rules (no multi-device needed:
AbstractMesh carries axis names/sizes without real devices)."""

import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import get
from repro.distributed.sharding import ShardingRules
from repro.models import lm as LM
from repro.models.params import param_specs


def mesh2(data=16, model=16):
    return AbstractMesh((data, model), ('data', 'model'))


def mesh3(pod=2, data=16, model=16):
    return AbstractMesh((pod, data, model), ('pod', 'data', 'model'))


def test_basic_rules():
    r = ShardingRules(mesh2())
    assert r.spec(('batch', 'seq', 'embed_act')) == P('data', None, None)
    assert r.spec(('embed', 'ffn')) == P('data', 'model')
    assert r.spec(('vocab', 'embed')) == P('model', 'data')


def test_multi_pod_batch_axis():
    r = ShardingRules(mesh3())
    assert r.spec(('batch',), (256,)) == P(('pod', 'data'))


def test_divisibility_fallback():
    r = ShardingRules(mesh2())
    # kv_heads = 2 cannot shard over 16-way model axis -> replicated
    assert r.spec(('none', 'none', 'kv_heads', 'head_dim'),
                  (1, 1, 2, 128)) == P(None, None, None, None)
    # 32 heads divide 16 -> sharded
    assert r.spec(('none', 'none', 'heads', 'head_dim'),
                  (1, 1, 32, 128)) == P(None, None, 'model', None)


def test_partial_axis_combination():
    r = ShardingRules(mesh3())
    # batch 32 divides pod*data=32 fully
    assert r.spec(('batch',), (32,)) == P(('pod', 'data'))
    # batch 2 only divides pod=2; data is dropped. (Single surviving axes
    # come back as the bare-string spelling: PartitionSpec('pod') !=
    # PartitionSpec(('pod',)) under == even though GSPMD treats them
    # identically.)
    assert r.spec(('batch',), (2,)) == P('pod')


def test_axis_dedupe_across_dims():
    """'data' must not be assigned to two dims of one array."""
    r = ShardingRules(mesh2())
    spec = r.spec(('cache_batch', 'cache_seq'), (128, 32768))
    assert spec == P('data', None)


def test_sequence_parallel_fallback_batch1():
    """batch=1 decode: cache_batch can't use 'data' -> cache_seq claims it
    (automatic sequence parallelism for long_500k)."""
    r = ShardingRules(mesh2())
    spec = r.spec(('none', 'cache_batch', 'cache_seq', 'kv_heads',
                   'head_dim'), (72, 1, 524288, 8, 128))
    assert spec == P(None, None, 'data', None, None)


def test_param_specs_cover_all_leaves():
    cfg = get('qwen2.5-3b')
    r = ShardingRules(mesh2())
    specs = param_specs(LM.model_defs(cfg), r)
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) > 10
    assert all(isinstance(s, P) for s in leaves)


def test_fsdp_embedding_spec():
    """Embedding: vocab over 'model', embed (d_model) over 'data' (ZeRO)."""
    cfg = get('qwen2.5-3b')
    r = ShardingRules(mesh2())
    defs = LM.model_defs(cfg)
    spec = r.spec(defs['embed'].axes, defs['embed'].shape)
    assert spec == P('model', 'data')


def test_moe_expert_sharding():
    cfg = get('deepseek-v2-lite-16b')
    r = ShardingRules(mesh2())
    defs = LM.model_defs(cfg)
    w1 = defs['layers']['ffn']['w1']          # stacked (L-1, e, d, ff)
    spec = r.spec(w1.axes, w1.shape)
    assert spec[1] == 'model'                 # experts axis -> EP over model
