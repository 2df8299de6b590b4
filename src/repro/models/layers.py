"""Core functional layers (no flax): norms, MLP, RoPE, embeddings.

Params are nested dicts of jnp arrays. ``init_*`` builds params; ``apply_*``
consumes them. Layer stacks are created with ``stack_init`` (vmapped init)
so model bodies can ``lax.scan`` over the stacked leading axis — this keeps
the HLO small (critical for the 61-layer 671B dry-run compile) and matches
the TPU-idiomatic MaxText pattern.
"""
from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


def stack_init(init_fn: Callable, key, n: int):
    """vmap an init over n split keys -> stacked params with leading dim n."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def apply_rmsnorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def apply_layernorm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# MLP: gated (swiglu / geglu) or plain (gelu)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, act: str, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"down": dense_init(k2, d_ff, d_model, dtype)}
    if act in ("swiglu", "geglu"):
        p["gate"] = dense_init(k1, d_model, d_ff, dtype)
        p["up"] = dense_init(k3, d_model, d_ff, dtype)
    else:
        p["up"] = dense_init(k1, d_model, d_ff, dtype)
    return p


def apply_mlp(p, x, act: str):
    if act == "swiglu":
        h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
    elif act == "geglu":
        h = jax.nn.gelu(x @ p["gate"], approximate=True) * (x @ p["up"])
    else:
        h = jax.nn.gelu(x @ p["up"], approximate=True)
    return h @ p["down"]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2), inline=True)
def _rope_tables(positions, dim: int, theta: float):
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's rotary frequencies (arXiv:2309.00071, as DeepSeek-V3 applies
    them): the dims that turn more than ``beta_fast`` times over
    ``original_max`` positions keep their frequency, those that turn fewer
    than ``beta_slow`` times are divided by ``factor``, and a linear ramp
    blends the dims between."""
    def dim_at(rot):
        return dim * math.log(original_max / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(dim_at(beta_fast)), 0)
    hi = min(math.ceil(dim_at(beta_slow)), dim - 1)
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return (base / factor * (1.0 - keep) + base * keep).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float = 10_000.0, inv_freq=None):
    """x: (..., seq, heads, head_dim); positions: (..., seq). ``inv_freq``
    (head_dim/2 frequencies, e.g. ``yarn_inv_freq``) replaces theta's."""
    dim = x.shape[-1]
    if inv_freq is None:
        cos, sin = _rope_tables(positions, dim, theta)  # (..., seq, dim/2)
    else:
        ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                            # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / lm head
# ---------------------------------------------------------------------------

def init_embed(key, vocab: int, dim: int, dtype=jnp.float32):
    return {"table": embed_init(key, vocab, dim, dtype)}


def apply_embed(p, tokens):
    return jnp.take(p["table"], tokens, axis=0)


def apply_lm_head(embed_params, x, head_params=None):
    """Tied (embed transpose) or untied head."""
    if head_params is not None:
        return x @ head_params["w"]
    table = embed_params["table"]
    return x @ table.T.astype(x.dtype)
