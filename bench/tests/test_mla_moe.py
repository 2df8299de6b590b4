"""DeepSeek-V3's cut (``bench/configs/deepseek-v3.json``) at a tiny size on
the CPU: the plain reference ``bench/reference/mla_moe.py`` against the
program, and the held-expert layer's share against the whole layer.

Tolerances, each with its reason:

* Weights built by the reference's own code from the same key equal the
  program's bit for bit.
* Program and reference both in float32 at ``highest`` precision sum the
  same products in different orders (blocked attention over the latent
  cache and the absorbed decode, sorted expert rows scattered back, against
  plain softmax and dense masked experts): F32_RTOL = 1e-4 of the largest
  value, ~1000 float32 roundings. Routing selects the same experts in
  both at this precision (no score lies within rounding of another here),
  so no selection flips.
* The program as configured (bf16) lies within BF16_RTOL = 0.05 of the
  largest logit of the float32 reference at 2 layers, and the float8
  control at least twice as far. (At the cell's depth a bf16 routing
  choice can flip against the float32 one where two experts' scores lie
  within rounding, which moves a position's logits by far more: PERF.md.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench.reference import common as C, mla_moe as R
from repro.configs import reduced
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.attention import mla_scale

F32_RTOL = 1e-4
BF16_RTOL = 0.05


def full():
    """The configuration the cell runs."""
    return ModelConfig(**H.load_cell("deepseek-serve-docs").config["model"])


def cut(**kw):
    """The cell's configuration at the tests' size: 1 dense + 1 expert
    layer, 8 experts in 4 groups of 2 (the best 2 kept), top-2, 4 held."""
    cfg = reduced(full()).replace(**kw)
    return cfg, dataclasses.asdict(cfg)


def f32(**kw):
    return cut(param_dtype="float32", compute_dtype="float32", **kw)


def tokens(cfg, b=2, s=20, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0,
                              cfg.vocab_size)


def exact(params, m, toks, control=False):
    with jax.default_matmul_precision("highest"):
        return R.logits(params, m, toks, C.Numerics(control=control))


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_cut_keeps_routing_consistent():
    cfg, _ = cut()
    assert (cfg.family, cfg.attention, cfg.router_type) == \
        ("moe", "mla", "noaux_tc")
    assert cfg.num_experts % cfg.n_group == 0
    assert cfg.topk_group * cfg.num_experts // cfg.n_group \
        >= cfg.experts_per_token
    assert 0 < cfg.experts_held < cfg.num_experts and cfg.yarn_factor == 40


def test_init_equals_program_init():
    cfg, m = cut()
    key = jax.random.PRNGKey(2 ** 31 + 3)
    prog = jax.jit(lambda k: T.init_lm(k, cfg))(key)
    mine = jax.jit(lambda k: R.init(m, k))(key)
    fp, _ = jax.tree_util.tree_flatten_with_path(prog)
    fr, _ = jax.tree_util.tree_flatten_with_path(mine)
    assert [jax.tree_util.keystr(k) for k, _ in fp] == \
        [jax.tree_util.keystr(k) for k, _ in fr]
    for (k, a), (_, b) in zip(fp, fr):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b)), k


@pytest.mark.parametrize("length", [1, 13, 20])
def test_prefill_then_decode_matches_reference(length):
    """One ``apply_moe_prefill`` of the prompt, then decode through the
    latent cache, against the reference's full forward at every position."""
    cfg, m = f32()
    S, total = 24, 20
    params = jax.jit(lambda k: R.init(m, k))(jax.random.PRNGKey(6))
    toks = tokens(cfg, s=total)
    padded = jnp.zeros((2, S), jnp.int32).at[:, :length].set(toks[:, :length])
    step = jax.jit(lambda p, t, c, i: T.apply_lm_decode(p, cfg, t, c, i))
    with jax.default_matmul_precision("highest"):
        lg, caches, load = jax.jit(
            lambda p, t, n, c: T.apply_moe_prefill(p, cfg, t, n, c))(
            params, padded, jnp.int32(length),
            T.init_caches(cfg, 2, S, jnp.float32))
        outs = [lg[:, 0]]
        for i in range(length, total):
            lg, caches = step(params, toks[:, i:i + 1], caches, jnp.int32(i))
            outs.append(lg[:, 0])
    want = exact(params, m, toks)[:, length - 1:]
    assert rel(jnp.stack(outs, 1), want) <= F32_RTOL
    # the load counts the prompt's rows only: padding is routed nowhere
    longer = jnp.zeros((2, 2 * S), jnp.int32).at[:, :S].set(padded)
    _, _, load2 = T.apply_moe_prefill(
        params, cfg, longer, jnp.int32(length),
        T.init_caches(cfg, 2, 2 * S, jnp.float32))
    assert load.shape == (cfg.num_layers - cfg.first_k_dense, cfg.experts_held)
    assert load.tolist() == load2.tolist()
    assert 0 < int(load.sum()) <= 2 * length * cfg.experts_per_token


def test_shares_add_up_to_the_whole_layer():
    """The routed parts of every expert share, plus the shared expert once,
    equal the uncut reference layer."""
    cfg, _ = f32()
    whole_cfg, whole_m = f32(experts_held=0)
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 9, cfg.d_model))
    E, n = cfg.num_experts, cfg.experts_held
    parts = []
    with jax.default_matmul_precision("highest"):
        for e0 in range(0, E, n):
            share = cfg.replace(expert_offset=e0)
            p = M.init_moe(key, share, jnp.float32)
            y, _ = M.apply_moe_held(p, share, x)
            parts.append(y - L.apply_mlp(p["shared"], x, "swiglu"))
        whole_p = M.init_moe(key, whole_cfg, jnp.float32)
        want = jax.vmap(lambda r: R.moe(whole_p, whole_m, r, C.Numerics()))(x)
    got = sum(parts) + L.apply_mlp(whole_p["shared"], x, "swiglu")
    assert rel(got, want) <= F32_RTOL


def test_every_token_on_one_held_expert_drops_nothing():
    """A bias that sends every token to held experts 0 and 1: each held
    expert computes all its rows, far past a capacity of 1.25 x the even
    share."""
    cfg, m = f32()
    p = M.init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    p["router_bias"] = p["router_bias"].at[:2].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 33, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, load = M.apply_moe_held(p, cfg, x)
        want = jax.vmap(lambda r: R.moe(p, m, r, C.Numerics()))(x)
    assert load.tolist() == [4 * 33, 4 * 33, 0, 0]
    assert M._held_tile(4 * 33, cfg.experts_per_token, cfg.num_experts) \
        < 4 * 33
    assert rel(y, want) <= F32_RTOL


def _route(cfg, p, x):
    w, idx, _ = M._route(p, cfg, x)
    return np.asarray(w), np.asarray(idx)


def test_bias_moves_selection_not_weights():
    cfg, _ = f32()
    p = M.init_moe(jax.random.PRNGKey(5), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 256, cfg.d_model))
    scores = np.asarray(jax.nn.sigmoid(x[0] @ p["router"]))
    w, idx = _route(cfg, p, x)
    w0, idx0 = _route(cfg, {**p, "router_bias": jnp.zeros_like(
        p["router_bias"])}, x)
    assert (np.sort(idx, -1) != np.sort(idx0, -1)).any()
    for ww, ii in ((w, idx), (w0, idx0)):
        picked = np.take_along_axis(scores, ii[0], -1)
        want = picked / picked.sum(-1, keepdims=True) \
            * cfg.routed_scaling_factor
        np.testing.assert_allclose(ww[0], want, rtol=1e-5)


def test_selection_stays_within_the_kept_groups():
    cfg, _ = f32()
    p = M.init_moe(jax.random.PRNGKey(7), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 512, cfg.d_model))
    _, idx = _route(cfg, p, x)
    biased = np.asarray(jax.nn.sigmoid(x[0] @ p["router"])
                        + p["router_bias"])
    G, per = cfg.n_group, cfg.num_experts // cfg.n_group
    best = np.sort(biased.reshape(-1, G, per), -1)[..., -2:].sum(-1)
    kept = np.argsort(-best, -1)[:, :cfg.topk_group]
    for row, chosen in enumerate(idx[0]):
        assert set(chosen // per) <= set(kept[row])
    # and the top-k by biased score alone would leave the kept groups
    free = np.argsort(-biased, -1)[:, :cfg.experts_per_token] // per
    assert any(not set(f) <= set(k) for f, k in zip(free, kept))


def test_yarn_frequencies_and_scale_by_hand():
    """DeepSeek-V3's rope dims (64, base 10^4, factor 40 over 4,096
    positions, beta 32 / 1): the correction range is dims 10..23 of 32
    (64 ln(4096/(32 2 pi)) / (2 ln 10^4) = 10.47 -> 10, 64 ln(4096/(2 pi))
    / (2 ln 10^4) = 22.5 -> 23); below it the frequency is kept, above it
    divided by 40, between blended linearly; mscale = 0.1 ln 40 + 1."""
    got = L.yarn_inv_freq(64, 10_000.0, 40.0, 4096, 32.0, 1.0)
    base = 1.0 / 10_000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0, 1)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(got, base / 40 * ramp + base * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(R.rope_freqs(dataclasses.asdict(full())),
                               got, rtol=1e-6)
    ms = 0.1 * np.log(40.0) + 1.0
    assert abs(ms - 1.3688879) < 1e-6
    assert abs(mla_scale(full()) - ms * ms / np.sqrt(192)) < 1e-7


def test_bf16_program_within_tolerance_and_control_outside():
    cfg, m = cut()
    params = jax.jit(lambda k: R.init(m, k))(jax.random.PRNGKey(8))
    toks = tokens(cfg, s=24)
    want = exact(params, m, toks)
    S = 24
    lg, caches, _ = T.apply_moe_prefill(
        params, cfg, toks, jnp.int32(S), T.init_caches(cfg, 2, S, jnp.float32))
    prog = rel(lg[:, 0], want[:, -1])
    ctrl = rel(exact(params, m, toks, control=True), want)
    assert prog <= BF16_RTOL
    assert ctrl >= 2 * prog


# ----------------------------------------------------------------------
# the cell's counts and metric readers
# ----------------------------------------------------------------------

def test_counted_weights_are_the_programs():
    """``bench/flops_mla_moe.py`` counts, at the cell's size, the program's
    weights bar the embedding, norms and selection bias: with every held
    expert touched, the decode bytes at no live position are those weights
    and the logits in bf16."""
    from bench import flops_mla_moe as F
    m = H.load_cell("deepseek-serve-docs").config["model"]
    shapes = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), full()))
    counted = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = jax.tree_util.keystr(path)
        if not any(s in name for s in ("embed", "norm", "ln1", "ln2",
                                       "router_bias")):
            counted += leaf.size
    V = F.padded_vocab(m)
    many = 10 ** 4                      # every held expert is touched
    assert F.touched_experts(m, many) == pytest.approx(m["experts_held"])
    got = F.decode_bytes(m, many, 0.0) / F.BF16 - many * V
    assert got == pytest.approx(counted, rel=1e-9)


def _run(events, served, kind="TPU v5 lite", spans=None):
    from types import SimpleNamespace
    cell = H.load_cell("deepseek-serve-docs")
    return SimpleNamespace(
        cell=cell, device={"kind": kind}, records={"served": served},
        device_trace={"events": events, "spans": spans or {}})


def _reader(name):
    return H.load_module(H.BENCH / "metrics" / f"{name}.py",
                         f"reader_{name.replace('.', '_')}").read


def test_decode_roofline_reads_the_decode_calls():
    from bench import flops_mla_moe as F
    from bench.trace_reduce import HOST_PLANE, MODULES_LINE, Event
    dev = "/device:TPU:0"
    ev = [Event(HOST_PLANE, "python", "serve.decode", 1.0, 1.1)]
    ev += [Event(dev, MODULES_LINE, "jit_decode_step(3)", 1.0 + 0.02 * i,
                 1.0 + 0.02 * i + 0.015) for i in range(4)]
    ev += [Event(dev, MODULES_LINE, "jit_prefill(9)", 0.5, 0.9)]
    served = [{"ok": True, "prompt_len": P, "gen_len": 64}
              for P in (512, 1000)]
    run = _run(ev, served)
    m, B = run.cell.config["model"], 8
    want = 100.0 * F.decode_bytes(m, B, 756 + 32) / 819e9 / 0.015
    assert _reader("moe_decode_roofline.serve")(run) == pytest.approx(want)
    assert _reader("moe_decode_roofline.serve")(_run(ev[1:], served)) \
        is None


def test_prefill_mfu_reads_prefills_overlapping_serve_steps():
    from bench import flops_mla_moe as F
    from bench.trace_reduce import MODULES_LINE, Event
    dev = "/device:TPU:0"
    ev = [Event(dev, MODULES_LINE, "jit_prefill(9)", 0.99, 1.79),
          Event(dev, MODULES_LINE, "jit_prefill(9)", 3.0, 4.2),
          Event(dev, MODULES_LINE, "jit_prefill(9)", 9.0, 19.0)]
    run = _run(ev, [], spans={"bench:serve": [(1.0, 2.0), (3.0, 5.0)]})
    m, t = run.cell.config["model"], run.cell.traffic
    flops = F.prefill_flops(m, t["requests_per_workflow"], t["max_len"])
    want = 100.0 * flops / (1.0 * 197e12)
    assert _reader("prefill_mfu.serve")(run) == pytest.approx(want)
    assert _reader("prefill_mfu.serve")(_run(ev, [])) is None


def test_expert_load_counter_and_its_reader():
    from repro.serving import engine as E
    before = E.METRICS.snapshot().get("prefill_expert_load_max")
    n0, s0 = (before["count"], before["sum"]) if before else (0, 0.0)
    rows = E.METRICS.counter("prefill_expert_rows", layer="1",
                             expert="2").value
    E.count_expert_load(np.array([[4, 4, 4, 4], [2, 6, 10, 2]]))
    after = E.METRICS.snapshot()["prefill_expert_load_max"]
    assert after["count"] == n0 + 1
    assert after["sum"] == pytest.approx(s0 + 10 / 4.5)
    assert E.METRICS.counter("prefill_expert_rows", layer="1",
                             expert="2").value == rows + 10
    assert _reader("expert_load_max.serve")(None) == \
        pytest.approx(after["sum"] / after["count"])
