"""The held-expert path reads an expert's weights in place, and only for
the row tiles that exist: one loop over the tiles that have rows, each
slicing its expert by (layer, expert) from the stacked params at its
matmuls. At DeepSeek-V3's cut (``bench/configs/deepseek-v3.json``) at the
tests' size on the CPU:

* the flat tile loop gives the per-expert scan's ``(y, load)`` bit for bit
  (the scan, the path's previous form, is kept here as the oracle): same
  tiles, same products, same order of accumulation;
* no value of the compiled decode or prefill program is one expert
  layer's stack of held experts: the layer scan slices none out (the
  per-expert scan did, so this fails there);
* held experts that no token reaches may hold NaN weights and the
  outputs stay finite: no tile multiplies them (the per-expert scan ran
  no tile for them either, so this guards the outputs, not the reads);
  a decode call's rows per held expert never exceed the experts held.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench.tests.test_mla_moe import cut, f32, tokens  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import engine as E  # noqa: E402


def scan_over_experts(x2d, idx2d, w2d, valid, ex, e0, tile):
    """The held path as a scan over every held expert, each running a
    loop of its ceil(rows / tile) tiles. ex: gate/up (El, D, F), down
    (El, F, D)."""
    T_, D = x2d.shape
    k = idx2d.shape[1]
    El = ex["gate"].shape[0]
    N = T_ * k
    e = idx2d.reshape(N) - e0
    held = (e >= 0) & (e < El)
    if valid is not None:
        held &= jnp.repeat(valid, k)
    key = jnp.where(held, e, El)
    order = jnp.argsort(key)
    stok = (order // k).astype(jnp.int32)
    sw = w2d.reshape(N)[order].astype(jnp.float32)
    starts = jnp.searchsorted(key[order], jnp.arange(El + 1), side="left")
    load = (starts[1:] - starts[:-1]).astype(jnp.int32)

    def expert(y, xs):
        s0, n, g, u, d = xs

        def one_tile(i, y):
            r = s0 + i * tile + jnp.arange(tile)
            w = jnp.where(r < s0 + n, sw[jnp.minimum(r, N - 1)], 0.0)
            tok = stok[jnp.minimum(r, N - 1)]
            xin = x2d[tok]
            h = jax.nn.silu(xin @ g) * (xin @ u)
            return y.at[tok].add((h @ d).astype(jnp.float32) * w[:, None])
        return jax.lax.fori_loop(0, (n + tile - 1) // tile, one_tile, y), None

    y, _ = jax.lax.scan(expert, jnp.zeros((T_, D), jnp.float32),
                        (starts[:-1], load, ex["gate"], ex["up"], ex["down"]))
    return y, load


def routed(cfg, p, b, s, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, cfg.d_model))
    w, idx, _ = M._route(p, cfg, x)
    return (x.reshape(b * s, -1), idx.reshape(b * s, -1),
            w.reshape(b * s, -1))


# (batch, positions, valid positions or None, bias that sends every token
# to held experts 0 and 1)
CASES = {"decode": (8, 1, None, False),
         "padded_prefill": (2, 24, 13, False),
         "all_rows_on_two_experts": (4, 33, None, True)}


@pytest.mark.parametrize("case", list(CASES))
def test_tile_loop_equals_the_per_expert_scan(case):
    b, s, length, skew = CASES[case]
    cfg, _ = f32()
    layers, layer = 3, 1
    p = M.init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    if skew:
        p["router_bias"] = p["router_bias"].at[:2].set(100.0)
    x2d, idx2d, w2d = routed(cfg, p, b, s, seed=4)
    valid = None if length is None else \
        jnp.repeat((jnp.arange(s) < length)[None], b, 0).reshape(b * s)
    # the layer's experts inside a stack of three layers' own
    stack = jax.tree.map(
        lambda a: jnp.stack([a * (1.0 + 0.5 * (i - layer))
                             for i in range(layers)]), p["experts"])
    tile = M._held_tile(b * s, cfg.experts_per_token, cfg.num_experts)
    e0, _ = M.held_experts(cfg)
    with jax.default_matmul_precision("highest"):
        want_y, want_load = jax.jit(
            lambda *a: scan_over_experts(*a, e0, tile))(
            x2d, idx2d, w2d, valid, p["experts"])
        got_y, got_load = jax.jit(
            lambda x, i, w, v, ex, l: M._held_expert_compute(
                x, i, w, v, ex, e0, tile, l))(
            x2d, idx2d, w2d, valid, stack, jnp.int32(layer))
    assert got_load.tolist() == want_load.tolist()
    assert int(got_load.sum()) > 0
    if skew:
        assert got_load.tolist() == [b * s, b * s, 0, 0]
        assert tile < b * s                     # several tiles an expert
    assert bool(jnp.array_equal(got_y, want_y))


@pytest.mark.parametrize("call", ["decode", "prefill"])
def test_no_expert_layer_stack_is_sliced_out(call):
    """At two expert layers, with F != D so gate/up and down differ in
    shape: no instruction of the compiled program yields one layer's held
    experts, (El, D, F) or (El, F, D); the weights are read from the
    (layers, El, ...) parameters by the tiles alone."""
    cfg, _ = cut(num_layers=3, moe_d_ff=48)
    El, D, F = cfg.experts_held, cfg.d_model, cfg.moe_d_ff
    params = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), cfg))
    caches = jax.eval_shape(lambda: T.init_caches(cfg, 2, 16))
    eng = E.ServingEngine(cfg, None, max_len=16)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    if call == "decode":
        lowered = eng._step.lower(
            params, jax.ShapeDtypeStruct((2, 1), jnp.int32), caches, i32)
    else:
        lowered = eng._prefill.lower(
            params, jax.ShapeDtypeStruct((2, eng._prefill_len), jnp.int32),
            i32, caches)
    text = lowered.compile().as_text()
    assert f"[{cfg.num_layers - 1},{El},{D},{F}]" in text
    one_layer = re.compile(rf"= \w+\[({El},{D},{F}|{El},{F},{D})\]")
    sliced = [ln for ln in text.splitlines() if one_layer.search(ln)]
    assert not sliced, sliced[:3]


def nan_unreached(params):
    """Bias every expert layer's routing to held experts 0 and 1, and give
    held experts 2 and 3 NaN weights."""
    moe = params["layers"]["moe"]
    moe["router_bias"] = moe["router_bias"].at[:, :2].set(100.0)
    moe["experts"] = jax.tree.map(lambda a: a.at[:, 2:].set(jnp.nan),
                                  moe["experts"])
    return params


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_unreached_experts_are_never_read(dtype):
    """Every expert layer routes to held experts 0 and 1 only, and held
    experts 2 and 3 hold NaN weights: prefill and decode logits stay
    finite, and the loads show no row on 2 or 3. A guard of the outputs:
    that the weights go unread shows in the compiled program
    (``test_no_expert_layer_stack_is_sliced_out``)."""
    cfg, _ = cut(param_dtype=dtype, compute_dtype=dtype)
    params = nan_unreached(T.init_lm(jax.random.PRNGKey(5), cfg))
    S, length = 24, 13
    toks = tokens(cfg, s=S)
    padded = jnp.where(jnp.arange(S)[None] < length, toks, 0)
    lg, caches, load = jax.jit(
        lambda p, t, n, c: T.apply_moe_prefill(p, cfg, t, n, c))(
        params, padded, jnp.int32(length), T.init_caches(cfg, 2, S))
    assert bool(jnp.all(jnp.isfinite(lg)))
    assert load[:, 2:].sum() == 0 and load[:, :2].min() == 2 * length
    step = jax.jit(lambda p, t, c, i: T._decode(p, cfg, t, c, i))
    for i in range(length, length + 3):
        lg, caches, load = step(params, toks[:, i:i + 1], caches,
                                jnp.int32(i))
        assert bool(jnp.all(jnp.isfinite(lg)))
        assert load.tolist() == [[2, 2, 0, 0]] * load.shape[0]
        # the held experts a call read (those with rows), of its slots
        assert 1 <= int((load > 0).sum()) <= load.size


def test_decode_returns_the_logits_and_caches_of_apply_lm_decode():
    cfg, _ = cut()
    params = T.init_lm(jax.random.PRNGKey(7), cfg)
    caches = T.init_caches(cfg, 2, 8)
    tok = tokens(cfg, s=1)
    lg, c, load = T._decode(params, cfg, tok, caches, jnp.int32(0))
    lg2, c2 = T.apply_lm_decode(params, cfg, tok, caches, jnp.int32(0))
    assert bool(jnp.array_equal(lg, lg2))
    assert jax.tree.all(jax.tree.map(jnp.array_equal, c, c2))
    assert load.shape == (cfg.num_layers - cfg.first_k_dense,
                          cfg.experts_held)
    # the all-experts path has no held loads to count
    whole, _ = cut(experts_held=0)
    params = T.init_lm(jax.random.PRNGKey(7), whole)
    assert T._decode(params, whole, tok, caches, jnp.int32(0))[2] is None

