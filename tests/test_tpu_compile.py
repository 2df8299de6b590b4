"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at real widths, and the one-chip check's train step and the
serving engine's prefill against the chip's memory. A compile that passes is not a chip run; it catches what
the chip's compiler refuses (unaligned blocks, unsupported primitives,
programs that do not fit) at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports every test file.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# usable HBM of one v5e chip (16 GiB less what the runtime reserves)
V5E_HBM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_flash_attention_compiles_at_stablelm_head_width(one_chip):
    from repro.kernels.flash_attention import flash_attention
    x = _sds((32, 2048, 64), jnp.bfloat16, one_chip)
    c = _compile(flash_attention, x, x, x)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_at_mamba2_370m_widths(one_chip):
    from repro.kernels.ssd_scan import ssd_scan
    BH, S, P, N = 32, 2048, 64, 128
    c = _compile(lambda x, a, b, cm: ssd_scan(x, a, b, cm, chunk=256),
                 _sds((BH, S, P), jnp.bfloat16, one_chip),
                 _sds((BH, S), jnp.float32, one_chip),
                 _sds((BH, S, N), jnp.bfloat16, one_chip),
                 _sds((BH, S, N), jnp.bfloat16, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_rmsnorm_compiles_at_d2048(one_chip):
    from repro.kernels.rmsnorm import rmsnorm
    c = _compile(rmsnorm, _sds((16384, 2048), jnp.bfloat16, one_chip),
                 _sds((2048,), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_chip_smoke_train_step_fits_one_chip(one_chip):
    """mamba2-370m at published widths and depth, batch 8 x 2048: the
    state, the batch and the step's temporaries fit in one chip's HBM."""
    import chip_smoke as CS
    from repro.training import train as TR
    cfg, tcfg = CS.model_config()
    key = jax.random.PRNGKey(0)
    state = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: TR.init_train_state(cfg, tcfg, key)))
    batch = {k: _sds(s.shape, s.dtype, one_chip)
             for k, s in CS.train_batch_shapes(cfg).items()}
    c = CS.jit_train_step(cfg, tcfg).lower(state, batch).compile()
    m = c.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert m.alias_size_in_bytes > 0          # the state is donated
    assert total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("arch,batch,max_len", [("stablelm-1.6b", 8, 272),
                                                ("mamba2-370m", 8, 256)])
def test_serving_prefill_fits_one_chip(one_chip, arch, batch, max_len):
    """The serving engine's one-call prefill (``jit_prefill``) at published
    widths and depth, at the serve cells' batch and cache length: weights,
    f32 caches and temporaries fit in one chip's HBM, and a KV cache is
    donated into the call rather than held twice."""
    from repro.configs import get_arch
    from repro.models import transformer as T
    from repro.serving.engine import ServingEngine
    cfg = get_arch(arch).model
    on_chip = lambda tree: jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: T.init_lm(jax.random.PRNGKey(0), cfg)))
    caches = on_chip(jax.eval_shape(
        lambda: T.init_caches(cfg, batch, max_len, jnp.float32)))
    eng = ServingEngine(cfg, None, max_len=max_len)
    tokens = _sds((batch, eng._prefill_len), jnp.int32, one_chip)
    c = eng._prefill.lower(params, tokens, _sds((), jnp.int32, one_chip),
                           caches).compile()
    m = c.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    if cfg.family == "dense":
        assert m.alias_size_in_bytes > 0
    assert total < V5E_HBM_BYTES, total


def _deepseek_share():
    import json
    from repro.configs.base import ModelConfig
    path = os.path.join(os.path.dirname(__file__), "..", "bench", "configs",
                        "deepseek-v3.json")
    with open(path) as f:
        return ModelConfig(**json.load(f)["model"])


@pytest.mark.parametrize("call", ["prefill", "decode"])
def test_deepseek_share_serving_fits_one_chip(one_chip, call):
    """DeepSeek-V3's one-chip share (7 layers, 8 of 256 experts held, a
    16,160-row vocabulary slice) at published widths, at the serve cell's
    batch 8 and cache length 2,112: the engine's ``jit_prefill`` and
    ``jit_decode_step`` with their weights, f32 latent caches and
    temporaries fit in one chip's HBM, and the prefill takes its caches
    donated."""
    from repro.models import transformer as T
    from repro.serving.engine import ServingEngine
    cfg, batch, max_len = _deepseek_share(), 8, 2112
    on_chip = lambda tree: jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: T.init_lm(jax.random.PRNGKey(0), cfg)))
    caches = on_chip(jax.eval_shape(
        lambda: T.init_caches(cfg, batch, max_len, jnp.float32)))
    eng = ServingEngine(cfg, None, max_len=max_len)
    i32 = _sds((), jnp.int32, one_chip)
    if call == "prefill":
        tokens = _sds((batch, eng._prefill_len), jnp.int32, one_chip)
        c = eng._prefill.lower(params, tokens, i32, caches).compile()
    else:
        tokens = _sds((batch, 1), jnp.int32, one_chip)
        c = eng._step.lower(params, tokens, caches, i32).compile()
    m = c.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(call, "argument", m.argument_size_in_bytes, "output",
          m.output_size_in_bytes, "temp", m.temp_size_in_bytes, "alias",
          m.alias_size_in_bytes, "total", total)
    if call == "prefill":
        assert m.alias_size_in_bytes > 0
    assert total < V5E_HBM_BYTES, total


def test_stablelm_pure_fsdp_train_step_fits_a_2x2_host(topo, no_compile_cache):
    """stablelm-1.6b at published widths and depth, AdamW, batch 16 x 2048,
    on a described 2x2 v5e mesh under the ``pure_fsdp`` rules
    ``launch/train.py`` uses: the donated state, the batch and the step's
    temporaries fit each chip's HBM (the state alone, 20.5 GB, fits none)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_arch
    from repro.sharding.ctx import use_mesh
    from repro.sharding.rules import (batch_specs, opt_state_specs,
                                      param_specs, rules_for, to_named)
    from repro.training import train as TR
    spec = get_arch("stablelm-1.6b")
    cfg, tcfg = spec.model, spec.train
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    rules = rules_for(cfg.name, "pure_fsdp")
    with use_mesh(mesh, rules, "pure_fsdp"):
        shapes = jax.eval_shape(
            lambda: TR.init_train_state(cfg, tcfg, jax.random.PRNGKey(0)))
        state_sh = {
            "params": to_named(param_specs(shapes["params"], mesh, rules, cfg,
                                           "pure_fsdp"), mesh),
            "opt": to_named(opt_state_specs(shapes["opt"], mesh, rules, cfg,
                                            "pure_fsdp"), mesh),
            "step": NamedSharding(mesh, P()),
        }
        state = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                             shapes, state_sh)
        batch = {k: jax.ShapeDtypeStruct((16, 2048), jnp.int32)
                 for k in ("tokens", "targets")}
        batch_sh = to_named(batch_specs(batch, mesh, rules), mesh)
        batch = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                             batch, batch_sh)
        step = jax.jit(TR.make_train_step(cfg, tcfg),
                       in_shardings=(state_sh, None),
                       out_shardings=(state_sh, None), donate_argnums=(0,))
        c = step.lower(state, batch).compile()
    m = c.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print("fsdp4 argument", m.argument_size_in_bytes, "output",
          m.output_size_in_bytes, "temp", m.temp_size_in_bytes, "alias",
          m.alias_size_in_bytes, "total", total)
    assert m.alias_size_in_bytes > 0          # the state is donated
    assert total < V5E_HBM_BYTES, total
