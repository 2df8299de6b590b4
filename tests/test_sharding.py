"""Pure-logic sharding tests (no multi-device runtime needed — mesh stubs).
Real-mesh behaviour is covered by tests/test_distributed.py subprocesses."""
from types import SimpleNamespace

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_arch
from repro.sharding.ctx import logical_to_spec
from repro.sharding.rules import (DEFAULT_RULES, FSDP_RULES,
                                  batch_logical_axes, cache_logical_axes,
                                  param_logical_axes, rules_for)


class FakeMesh(SimpleNamespace):
    pass


MESH = FakeMesh(shape={"data": 16, "model": 16})
MESH3 = FakeMesh(shape={"pod": 2, "data": 16, "model": 16})


def spec(axes, shape, mesh=MESH, rules=DEFAULT_RULES):
    return logical_to_spec(axes, shape, mesh, rules)


def test_basic_resolution():
    assert spec(("vocab", "embed"), (51200, 2048)) == P("model")
    assert spec(("embed", "mlp"), (2048, 5632)) == P(None, "model")
    assert spec(("batch", None), (256, 4096)) == P("data")


def test_divisibility_fallback_drops_axis():
    # 20 heads % 16 -> dropped
    assert spec(("batch", "kv_heads", "kv_seq", None),
                (32, 20, 32768, 64)) == P("data", None, "model")
    # divisible heads win before kv_seq
    assert spec(("batch", "kv_heads", "kv_seq", None),
                (32, 32, 32768, 64)) == P("data", "model")
    # batch smaller than the data axis -> batch unsharded too
    assert spec(("batch", "kv_heads", "kv_seq", None),
                (8, 20, 32768, 64)) == P(None, None, "model")


def test_no_double_axis_use():
    s = spec(("vocab", "mlp"), (512, 512))
    # both want 'model' but an axis is used at most once
    assert s == P("model") or s == P("model", None)


def test_multipod_batch_axes():
    assert spec(("batch", None), (256, 4096), mesh=MESH3) == P(("pod", "data"))
    # batch=1 -> nothing shards
    assert spec(("batch", None), (1, 4096), mesh=MESH3) == P()


def test_fsdp_rules_shard_embed_dim():
    assert logical_to_spec(("embed", "mlp"), (7168, 2048), MESH,
                           FSDP_RULES) == P("data", "model")
    assert rules_for("deepseek-v3-671b") is FSDP_RULES
    assert rules_for("stablelm-1.6b") is DEFAULT_RULES


STRATEGIES = ("baseline", "moe_a2a", "moe_a2a_seqshard", "moe_rs",
              "dp_zero1", "pure_fsdp")


@pytest.mark.parametrize("aid", [a for a in ARCH_IDS
                                 if get_arch(a).model.num_experts])
def test_expert_fsdp_follows_the_rules_in_force(aid):
    """The expert weights are FSDP-gathered exactly where the rules shard
    their ``embed`` dim over ``data``: for every strategy whose rules put
    the experts on ``model`` (where the EP paths run), that is DeepSeek-V3
    (in FSDP_ARCHS) and not OLMoE, and no mesh without a data axis."""
    from repro.models.moe import experts_fsdp
    deepseek = get_arch(aid).model.name.startswith("deepseek")
    for strategy in STRATEGIES:
        rules = rules_for(aid, strategy)
        if rules.get("expert") != "model":
            continue
        assert experts_fsdp(MESH, rules) == deepseek, strategy
        assert not experts_fsdp(FakeMesh(shape={"model": 16}), rules)


class _K:
    def __init__(self, k):
        self.key = k


def _axes(path, shape):
    return param_logical_axes(tuple(_K(p) for p in path), shape)


def test_param_path_mapping():
    assert _axes(("embed", "table"), (51200, 2048)) == ("vocab", "embed")
    assert _axes(("layers", "attn", "wq"), (24, 2048, 2048)) == \
        (None, "embed", "heads")
    assert _axes(("layers", "moe", "experts", "gate"),
                 (16, 64, 2048, 1024)) == (None, "expert", "embed", "mlp")
    assert _axes(("layers", "ssm", "in_x"), (48, 1024, 2048)) == \
        (None, "embed", "ssm_inner")
    assert _axes(("groups", "0", "ssm", "conv_x"), (6, 6, 4, 4224)) == \
        (None, None, None, "ssm_inner")
    assert _axes(("final_norm", "scale"), (2048,)) == (None,)
    assert _axes(("lm_head", "w"), (2048, 51200)) == ("embed", "vocab")


def test_cache_path_mapping():
    def c(path, shape):
        return cache_logical_axes(tuple(_K(p) for p in path), shape)
    assert c(("layers", "k"), (24, 8, 32, 1024, 128)) == \
        (None, "batch", "kv_heads", "kv_seq", None)
    assert c(("layers", "c_kv"), (58, 8, 32768, 512)) == \
        (None, "batch", "kv_seq", None)
    assert c(("layers", "state"), (48, 8, 32, 64, 128)) == \
        (None, "batch", "ssm_heads", None, None)


def test_head_aware_fallback():
    """kv_heads=8 vs TP=16 -> wk/wv switch to contraction sharding."""
    from repro.sharding.rules import _head_aware
    cfg = get_arch("mistral-nemo-12b").model
    fn = _head_aware(param_logical_axes, cfg, MESH)
    assert fn(tuple(_K(p) for p in ("layers", "attn", "wk")),
              (40, 5120, 1024)) == (None, "tp", None)
    # q heads divide -> unchanged
    assert fn(tuple(_K(p) for p in ("layers", "attn", "wq")),
              (40, 5120, 4096)) == (None, "embed", "heads")


def test_batch_mapping():
    def b(path, shape):
        return batch_logical_axes(tuple(_K(p) for p in path), shape)
    assert b(("tokens",), (256, 4096)) == ("batch", None)
    assert b(("patches",), (256, 256, 2048)) == ("batch", None, None)
