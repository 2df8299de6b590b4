"""Plain float32 reference of DeepSeek-V3's block (arXiv:2412.19437;
hf:deepseek-ai/DeepSeek-V3 config.json), as one chip's expert share of it.

The model: ``first_k_dense`` layers of ``h + MLA(Norm(h))`` then
``h + MLP(Norm(h))`` (SwiGLU of width ``d_ff``), then expert layers of
``h + MLA(Norm(h))`` then ``h + MoE(Norm(h))``, a final norm and a separate
output head.

* MLA, computed unabsorbed over the whole sequence: q = W_qb(Norm(W_qa x))
  split into a no-rope part (``qk_nope_dim``) and a rope part
  (``qk_rope_dim``); the latent c = Norm(first ``kv_lora_rank`` of W_kva x)
  expands through W_kvb to per-head no-rope keys and values, and the last
  ``qk_rope_dim`` of W_kva x is one rope key shared by all heads. Causal
  softmax at scale mscale^2 / sqrt(nope + rope).
* Rope: YaRN (arXiv:2309.00071) as DeepSeek-V3 applies it: frequencies
  base^(-2i/d) kept where a dim turns more than ``beta_fast`` times over
  ``original_max`` positions, divided by ``factor`` where it turns fewer
  than ``beta_slow`` times, blended linearly between; mscale = 0.1
  ln(factor) + 1 (``mscale_all_dim`` 1) squared on the softmax scale.
* Routing, ``noaux_tc``: s = sigmoid(x W_r) over all ``num_experts``;
  selection by s + bias: the experts fall in ``n_group`` groups, a group
  scores the sum of its two best biased scores, the ``topk_group`` best
  groups are kept and the ``experts_per_token`` best experts within them
  chosen; their weights are their unbiased s, normalised to sum 1, times
  ``routed_scaling_factor``.
* Experts: each held expert (``expert_offset`` .. + ``experts_held``) is
  computed for every token and masked by the routing weight, plus the
  shared expert (SwiGLU of width ``moe_d_ff`` x ``num_shared_experts``).

Departures from the published model, in the program and the reference
alike:

* no multi-token prediction module (``num_nextn_predict_layers`` 1 -> 0);
* rope in rotate-half form: the checkpoint's interleaved pairs are a fixed
  permutation of the rope columns of W_qb and W_kva, which random weights
  do not see;
* the experts this chip does not hold give nothing: their part of each
  expert layer is left out, as one chip of an expert-parallel deployment
  computes only its own experts' part.

Computed one batch row at a time and, in attention, ``HEAD_BLOCK`` heads at
a time, so that a long prompt batch fits beside the weights.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common as C

HEAD_BLOCK = 16


def _experts(m):
    n = m["experts_held"] or m["num_experts"]
    return m["expert_offset"], n


def init(m: Dict[str, Any], key):
    dtype = C.DTYPES[m["param_dtype"]]
    D, H, E = m["d_model"], m["num_heads"], m["num_experts"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    F = m["moe_d_ff"]
    V = C.padded_vocab(m["vocab_size"], m["pad_vocab_multiple"])
    e0, n = _experts(m)
    ks = jax.random.split(key, 8)

    def mla(k):
        a = jax.random.split(k, 6)
        return {"wq_a": C.dense_init(a[0], D, qr, dtype),
                "q_norm": {"scale": C.ones(qr, dtype)},
                "wq_b": C.dense_init(a[1], qr, H * (nope + rope), dtype),
                "wkv_a": C.dense_init(a[2], D, kvr + rope, dtype),
                "kv_norm": {"scale": C.ones(kvr, dtype)},
                "wkv_b": C.dense_init(a[3], kvr, H * (nope + vd), dtype),
                "wo": C.dense_init(a[4], H * vd, D, dtype)}

    def mlp(k, width):
        a = jax.random.split(k, 3)
        return {"down": C.dense_init(a[1], width, D, dtype),
                "gate": C.dense_init(a[0], D, width, dtype),
                "up": C.dense_init(a[2], D, width, dtype)}

    def dense_layer(k):
        ka, km = jax.random.split(k)
        return {"ln1": {"scale": C.ones(D, dtype)}, "attn": mla(ka),
                "ln2": {"scale": C.ones(D, dtype)}, "mlp": mlp(km, m["d_ff"])}

    def expert_layer(k):
        ka, km = jax.random.split(k)
        a = jax.random.split(km, 6)

        def held(kk, n_in, n_out):
            keys = jax.random.split(kk, E)[e0:e0 + n]
            return jax.lax.map(lambda x: C.dense_init(x, n_in, n_out, dtype),
                               keys)
        moe = {"router": C.dense_init(a[0], D, E, jnp.float32),
               "experts": {"gate": held(a[1], D, F), "up": held(a[2], D, F),
                           "down": held(a[3], F, D)},
               "router_bias": jax.random.normal(a[5], (E,), jnp.float32)
               * m["router_bias_scale"]}
        if m["num_shared_experts"]:
            moe["shared"] = mlp(a[4], F * m["num_shared_experts"])
        return {"ln1": {"scale": C.ones(D, dtype)}, "attn": mla(ka),
                "ln2": {"scale": C.ones(D, dtype)}, "moe": moe}

    p = {"embed": {"table": (jax.random.normal(ks[0], (V, D), jnp.float32)
                             * 0.02).astype(dtype)},
         "final_norm": {"scale": C.ones(D, dtype)},
         "lm_head": {"w": C.dense_init(ks[1], D, V, dtype)},
         "layers": C.stack_layers(expert_layer, ks[2],
                                  m["num_layers"] - m["first_k_dense"])}
    if m["first_k_dense"]:
        p["dense_layers"] = C.stack_layers(dense_layer, ks[3],
                                           m["first_k_dense"])
    return p


def mscale(factor: float, scale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0


def rope_freqs(m) -> np.ndarray:
    d, base = m["qk_rope_dim"], m["rope_theta"]
    freqs = 1.0 / base ** (np.arange(0, d, 2) / d)
    f = m["yarn_factor"]
    if not f:
        return freqs
    turns = lambda r: d * math.log(m["yarn_original_max"] / (r * 2 * math.pi)) \
        / (2 * math.log(base))
    lo = max(math.floor(turns(m["yarn_beta_fast"])), 0)
    hi = min(math.ceil(turns(m["yarn_beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return freqs / f * ramp + freqs * (1 - ramp)


def softmax_scale(m) -> float:
    s = (m["qk_nope_dim"] + m["qk_rope_dim"]) ** -0.5
    if m["yarn_factor"]:
        s *= mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2
    return s


def rope(x, freqs):
    """x: (s, heads, d) at positions 0..s-1, rotate-half form."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, m, x, num: C.Numerics):
    """MLA over one row x: (s, D)."""
    s = x.shape[0]
    H, eps = m["num_heads"], m["norm_eps"]
    kvr, nope, vd = m["kv_lora_rank"], m["qk_nope_dim"], m["v_head_dim"]
    freqs = rope_freqs(m)
    q = num.mm(C.rmsnorm(num.mm(x, p["wq_a"]), p["q_norm"]["scale"], eps),
               p["wq_b"]).reshape(s, H, -1)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], freqs)
    kv = num.mm(x, p["wkv_a"])
    c = C.rmsnorm(kv[:, :kvr], p["kv_norm"]["scale"], eps)
    k_rope = rope(kv[:, None, kvr:], freqs)[:, 0]               # (s, rope)
    kvb = num.mm(c, p["wkv_b"]).reshape(s, H, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = softmax_scale(m)

    def heads(xs):
        qn, qr, kn, vv = xs                                       # (s, hb, .)
        sc = (num.einsum("qhd,khd->hqk", qn, kn)
              + num.einsum("qhr,kr->hqk", qr, k_rope)) * scale
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return num.einsum("hqk,khd->qhd", w, vv)

    hb = min(HEAD_BLOCK, H)
    blocks = lambda a: jnp.moveaxis(a.reshape(s, H // hb, hb, -1), 1, 0)
    o = jax.lax.map(heads, (blocks(q_nope), blocks(q_rope), blocks(k_nope),
                            blocks(v)))                       # (H/hb,s,hb,vd)
    o = jnp.moveaxis(o, 0, 1).reshape(s, H * vd)
    return num.mm(o, p["wo"])


def mlp(p, x, num: C.Numerics):
    return num.mm(jax.nn.silu(num.mm(x, p["gate"])) * num.mm(x, p["up"]),
                  p["down"])


def route(p, m, x, num: C.Numerics):
    """noaux_tc over one row x: (s, D) -> dense routing weights (s, E)."""
    E, G, k = m["num_experts"], m["n_group"], m["experts_per_token"]
    scores = jax.nn.sigmoid(num.mm(x, p["router"]))
    biased = scores + p["router_bias"]
    if G > 1:
        grp = jnp.sort(biased.reshape(-1, G, E // G), axis=-1)
        best = jnp.sum(grp[..., -min(2, E // G):], axis=-1)        # (s, G)
        rank = jnp.argsort(jnp.argsort(-best, axis=-1), axis=-1)
        keep = jnp.repeat(rank < m["topk_group"], E // G, axis=-1)
        biased = jnp.where(keep, biased, -jnp.inf)
    chosen = jnp.argsort(-biased, axis=-1)[:, :k]
    w = jnp.take_along_axis(scores, chosen, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * m["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(w)


def moe(p, m, x, num: C.Numerics):
    """The held experts' part and the shared expert, over one row."""
    e0, n = _experts(m)
    weights = route(p, m, x, num)[:, e0:e0 + n]                 # (s, n)

    def expert(y, xs):
        ex, w = xs
        return y + w[:, None] * mlp(ex, x, num), None

    y0 = mlp(p["shared"], x, num) if "shared" in p else jnp.zeros_like(x)
    y, _ = jax.lax.scan(expert, y0, (p["experts"], weights.T))
    return y


def hidden(params, m, tokens, num: C.Numerics):
    """Final-normed hidden states (b, s, D) in float32, a row at a time."""
    eps = m["norm_eps"]

    def dense_layer(h, lp):
        h = h + attention(lp["attn"], m, C.rmsnorm(h, lp["ln1"]["scale"], eps),
                          num)
        return h + mlp(lp["mlp"], C.rmsnorm(h, lp["ln2"]["scale"], eps),
                       num), None

    def expert_layer(h, lp):
        h = h + attention(lp["attn"], m, C.rmsnorm(h, lp["ln1"]["scale"], eps),
                          num)
        return h + moe(lp["moe"], m, C.rmsnorm(h, lp["ln2"]["scale"], eps),
                       num), None

    def row(tok):
        h = jnp.take(params["embed"]["table"], tok, axis=0).astype(jnp.float32)
        if "dense_layers" in params:
            h, _ = jax.lax.scan(dense_layer, h, params["dense_layers"])
        h, _ = jax.lax.scan(expert_layer, h, params["layers"])
        return C.rmsnorm(h, params["final_norm"]["scale"], eps)

    return jax.lax.map(row, tokens)


def head(params, num: C.Numerics):
    w = params["lm_head"]["w"]
    return lambda h: num.mm(h, w)


def logits(params, m, tokens, num: C.Numerics):
    return head(params, num)(hidden(params, m, tokens, num))
