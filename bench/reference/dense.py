"""Plain float32 reference of a dense decoder-only transformer (the block of
StableLM-2, hf:stabilityai/stablelm-2-1_6b, as the configuration states it).

Each layer is ``h + Attn(Norm(h))`` then ``h + MLP(Norm(h))``: causal
multi-head attention with rotary position embeddings (rotate-half form,
base ``rope_theta``), softmax at scale 1/sqrt(head_dim), and a SwiGLU MLP
``down(silu(gate x) * up x)``. The output head is a separate matrix.

Departures of the configuration as run from the published StableLM-2-1.6B,
which the reference follows because they are the model as configured:
RMSNorm without bias in place of LayerNorm; rotary embeddings over the
whole head in place of its first quarter; no biases on the q, k and v
projections.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference import common as C


def init(m: Dict[str, Any], key):
    dtype = C.DTYPES[m["param_dtype"]]
    D, F = m["d_model"], m["d_ff"]
    H, KH, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    V = C.padded_vocab(m["vocab_size"], m["pad_vocab_multiple"])
    ks = jax.random.split(key, 8)

    def layer(k):
        ka, km = jax.random.split(k)
        qa = jax.random.split(ka, 4)
        mm = jax.random.split(km, 3)
        return {
            "ln1": {"scale": C.ones(D, dtype)},
            "attn": {"wq": C.dense_init(qa[0], D, H * hd, dtype),
                     "wk": C.dense_init(qa[1], D, KH * hd, dtype),
                     "wv": C.dense_init(qa[2], D, KH * hd, dtype),
                     "wo": C.dense_init(qa[3], H * hd, D, dtype)},
            "ln2": {"scale": C.ones(D, dtype)},
            "mlp": {"down": C.dense_init(mm[1], F, D, dtype),
                    "gate": C.dense_init(mm[0], D, F, dtype),
                    "up": C.dense_init(mm[2], D, F, dtype)},
        }

    return {"embed": {"table": (jax.random.normal(ks[0], (V, D), jnp.float32)
                                * 0.02).astype(dtype)},
            "final_norm": {"scale": C.ones(D, dtype)},
            "lm_head": {"w": C.dense_init(ks[1], D, V, dtype)},
            "layers": C.stack_layers(layer, ks[2], m["num_layers"])}


def rope(x, theta: float):
    """x: (b, s, heads, hd) at positions 0..s-1."""
    hd, s = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, m, x, num: C.Numerics):
    b, s, _ = x.shape
    H, KH, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = rope(num.mm(x, p["wq"]).reshape(b, s, H, hd), m["rope_theta"])
    k = rope(num.mm(x, p["wk"]).reshape(b, s, KH, hd), m["rope_theta"])
    v = num.mm(x, p["wv"]).reshape(b, s, KH, hd)
    k, v = jnp.repeat(k, H // KH, axis=2), jnp.repeat(v, H // KH, axis=2)
    scores = num.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = num.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, H * hd)
    return num.mm(o, p["wo"])


def mlp(p, x, num: C.Numerics):
    return num.mm(jax.nn.silu(num.mm(x, p["gate"])) * num.mm(x, p["up"]),
                  p["down"])


def hidden(params, m, tokens, num: C.Numerics):
    """Final-normed hidden states (b, s, D) in float32."""
    eps = m["norm_eps"]
    h = jnp.take(params["embed"]["table"], tokens, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def layer(h, lp):
        h = h + attention(lp["attn"], m, C.rmsnorm(h, lp["ln1"]["scale"], eps), num)
        return h + mlp(lp["mlp"], C.rmsnorm(h, lp["ln2"]["scale"], eps), num), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    return C.rmsnorm(h, params["final_norm"]["scale"], eps)


def head(params, num: C.Numerics):
    w = params["lm_head"]["w"]
    return lambda h: num.mm(h, w)


def logits(params, m, tokens, num: C.Numerics):
    return head(params, num)(hidden(params, m, tokens, num))


def loss(params, m, batch, num: C.Numerics):
    h = hidden(params, m, batch["tokens"], num)
    return C.blocked_cross_entropy(head(params, num), h, batch["targets"])
