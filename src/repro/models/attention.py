"""Attention: GQA/MHA and MLA (DeepSeek latent), full + prefill + decode paths.

Full-sequence attention is *blockwise* (lax.scan over KV blocks with online
softmax — flash-attention semantics at the XLA level) so that 32k-token
prefill never materializes the (S x S) score matrix. The per-block body is
wrapped in ``jax.checkpoint`` so the autodiff backward recomputes block
scores instead of saving O(S^2) residuals. The GQA prefill path is the
full path that also writes the prompt's rotated keys and values into the
decode cache; the MLA prefill writes the latent cache (``c_kv``, ``k_rope``)
and attends one batch row at a time, so its scores stay one row's size.

Decode attends a single new token against a KV cache laid out
(batch, kv_heads, seq, head_dim) so the sharding resolver prefers
head-sharding and falls back to split-KV sequence sharding when
``kv_heads % TP != 0`` (flash-decoding pattern).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding.ctx import shard

KV_BLOCK = 1024


# ---------------------------------------------------------------------------
# GQA / MHA
# ---------------------------------------------------------------------------

def init_attention(key, cfg, d_in: Optional[int] = None, dtype=jnp.float32):
    d_in = d_in or cfg.d_model
    hd, H, KH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(k1, d_in, H * hd, dtype),
        "wk": L.dense_init(k2, d_in, KH * hd, dtype),
        "wv": L.dense_init(k3, d_in, KH * hd, dtype),
        "wo": L.dense_init(k4, H * hd, cfg.d_model, dtype),
    }


def _block_attn(q, k, v, qpos, kpos, prefix_len, scale):
    """One KV block of online-softmax attention.

    q: (B, H, Sq, hd); k/v: (B, H, Bk, hd); returns (acc, m, l) update terms.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    mask = qpos[None, None, :, None] >= kpos[None, None, None, :]
    if prefix_len is not None:
        bidir = kpos[None, None, None, :] < prefix_len
        mask = jnp.logical_or(mask, bidir)
    s = jnp.where(mask, s, -1e30)
    m_blk = jnp.max(s, axis=-1)                      # (B,H,Sq)
    p = jnp.exp(s - m_blk[..., None])
    l_blk = jnp.sum(p, axis=-1)
    o_blk = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return o_blk, m_blk, l_blk


def _merge(carry, o_blk, m_blk, l_blk):
    acc, m, l = carry
    m_new = jnp.maximum(m, m_blk)
    a = jnp.exp(m - m_new)
    b = jnp.exp(m_blk - m_new)
    acc = acc * a[..., None] + o_blk * b[..., None]
    l = l * a + l_blk * b
    return acc, m_new, l


def blockwise_attention(q, k, v, qpos, kpos, prefix_len=None,
                        block: int = KV_BLOCK, scale: Optional[float] = None):
    """q: (B,H,Sq,hd), k/v: (B,H,Sk,hd). Returns (B,H,Sq,hd)."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    block = min(block, Sk)
    pad = (-Sk) % block
    if pad:  # pad keys; sentinel positions are masked out by the causal test
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kpos = jnp.pad(kpos, (0, pad), constant_values=jnp.int32(2 ** 30))
        Sk += pad
    nblk = Sk // block

    kb = k.reshape(B, H, nblk, block, hd).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nblk, block, v.shape[-1]).transpose(2, 0, 1, 3, 4)
    pb = kpos.reshape(nblk, block)

    @jax.checkpoint
    def body(carry, inp):
        kblk, vblk, kposblk = inp
        o_blk, m_blk, l_blk = _block_attn(q, kblk, vblk, qpos, kposblk,
                                          prefix_len, scale)
        return _merge(carry, o_blk, m_blk, l_blk), None

    acc0 = jnp.zeros((B, H, Sq, v.shape[-1]), jnp.float32)
    m0 = jnp.full((B, H, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _qkv(p, cfg, x, positions):
    """x: (B,S,D_in) -> rotated q (B,S,H,hd), rotated k and v (B,S,KH,hd)."""
    B, S, _ = x.shape
    hd, H, KH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, S, H, hd)
    k = (x @ p["wk"].astype(dt)).reshape(B, S, KH, hd)
    v = (x @ p["wv"].astype(dt)).reshape(B, S, KH, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend(p, q, k, v, positions, prefix_len):
    """Blockwise attention of rotated q over k, v, then the out projection."""
    B, S, H, hd = q.shape
    KH = k.shape[2]
    dt = q.dtype
    if KH != H:
        rep = H // KH
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    q = shard(q.transpose(0, 2, 1, 3), "batch", "heads", "seq_q", None)
    k = shard(k.transpose(0, 2, 1, 3), "batch", "heads", None, None)
    v = shard(v.transpose(0, 2, 1, 3), "batch", "heads", None, None)
    qpos = positions[0] if positions.ndim == 2 else positions
    out = blockwise_attention(q, k, v, qpos, qpos, prefix_len)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return out @ p["wo"].astype(dt)


def apply_attention_full(p, cfg, x, positions, prefix_len=None):
    """x: (B,S,D_in) -> (B,S,D). Causal (or prefix-LM) full attention."""
    q, k, v = _qkv(p, cfg, x, positions)
    return _attend(p, q, k, v, positions, prefix_len)


def apply_attention_prefill(p, cfg, x, positions, cache):
    """Causal full attention that also writes the rotated k and v of every
    position of x into the cache (B,KH,S_cache,hd) from position 0: the
    cache ``apply_attention_decode`` leaves after S calls, up to rounding.

    Returns (out (B,S,D), new_cache)."""
    q, k, v = _qkv(p, cfg, x, positions)
    out = _attend(p, q, k, v, positions, None)
    k_c = jax.lax.dynamic_update_slice(
        cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype), (0, 0, 0, 0))
    v_c = jax.lax.dynamic_update_slice(
        cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype), (0, 0, 0, 0))
    k_c = shard(k_c, "batch", "kv_heads", "kv_seq", None)
    v_c = shard(v_c, "batch", "kv_heads", "kv_seq", None)
    return out, {"k": k_c, "v": v_c}


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    hd, KH = cfg.head_dim, cfg.num_kv_heads
    return {
        "k": jnp.zeros((batch, KH, max_len, hd), dtype),
        "v": jnp.zeros((batch, KH, max_len, hd), dtype),
    }


def apply_attention_decode(p, cfg, x, cache, index):
    """x: (B,1,D_in); cache k/v: (B,KH,S,hd); index: scalar current position.

    Returns (out (B,1,D), new_cache).
    """
    B = x.shape[0]
    hd, H, KH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, 1, H, hd)
    k = (x @ p["wk"].astype(dt)).reshape(B, 1, KH, hd)
    v = (x @ p["wv"].astype(dt)).reshape(B, 1, KH, hd)
    pos = jnp.full((B, 1), index, jnp.int32)
    q = L.apply_rope(q, pos, cfg.rope_theta)
    k = L.apply_rope(k, pos, cfg.rope_theta)

    k_c = jax.lax.dynamic_update_slice(
        cache["k"], k.transpose(0, 2, 1, 3).astype(cache["k"].dtype), (0, 0, index, 0))
    v_c = jax.lax.dynamic_update_slice(
        cache["v"], v.transpose(0, 2, 1, 3).astype(cache["v"].dtype), (0, 0, index, 0))
    k_c = shard(k_c, "batch", "kv_heads", "kv_seq", None)
    v_c = shard(v_c, "batch", "kv_heads", "kv_seq", None)

    G = H // KH
    qg = q.reshape(B, KH, G, hd)                       # (B,KH,G,hd)
    s = jnp.einsum("bkgd,bksd->bkgs", qg.astype(jnp.float32),
                   k_c.astype(jnp.float32)) * hd ** -0.5
    S = k_c.shape[2]
    valid = jnp.arange(S)[None, None, None, :] <= index
    s = jnp.where(valid, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", w.astype(v_c.dtype), v_c)
    o = o.reshape(B, 1, H * hd).astype(dt)
    return o @ p["wo"].astype(dt), {"k": k_c, "v": v_c}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg, dtype=jnp.float32):
    D, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": L.dense_init(ks[0], D, qr, dtype),
        "q_norm": L.init_rmsnorm(qr, dtype),
        "wq_b": L.dense_init(ks[1], qr, H * (nope + rope), dtype),
        "wkv_a": L.dense_init(ks[2], D, kvr + rope, dtype),
        "kv_norm": L.init_rmsnorm(kvr, dtype),
        "wkv_b": L.dense_init(ks[3], kvr, H * (nope + vd), dtype),
        "wo": L.dense_init(ks[4], H * vd, D, dtype),
    }


def mla_rope_freqs(cfg):
    """The rope frequencies of MLA's decoupled key and query dims: YaRN's
    when ``yarn_factor`` is set, else None (plain rope at ``rope_theta``)."""
    if not cfg.yarn_factor:
        return None
    if L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) != \
            L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim):
        raise ValueError("YaRN with mscale != mscale_all_dim scales cos and "
                         "sin; not supported")
    return L.yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor,
                           cfg.yarn_original_max, cfg.yarn_beta_fast,
                           cfg.yarn_beta_slow)


def mla_scale(cfg) -> float:
    """Softmax scale: 1/sqrt(q head dim), times YaRN's mscale squared."""
    m = (L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
         if cfg.yarn_factor else 1.0)
    return m * m * (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5


def _mla_qkv(p, cfg, x, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = x.dtype
    freqs = mla_rope_freqs(cfg)
    q = L.apply_rmsnorm(p["q_norm"], x @ p["wq_a"].astype(dt), cfg.norm_eps)
    q = (q @ p["wq_b"].astype(dt)).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta, freqs)

    kv = x @ p["wkv_a"].astype(dt)                    # (B,S,kvr+rope)
    c_kv = L.apply_rmsnorm(p["kv_norm"], kv[..., : cfg.kv_lora_rank], cfg.norm_eps)
    k_rope = kv[..., cfg.kv_lora_rank:][..., None, :]  # (B,S,1,rope)
    k_rope = L.apply_rope(k_rope, positions, cfg.rope_theta, freqs)
    return q_nope, q_rope, c_kv, k_rope


def _mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope, qpos,
                block: int = KV_BLOCK):
    """Causal attention of MLA's queries over the keys and values its
    latent expands to (not absorbed: every position is a query).
    Returns the heads' outputs (B,S,H*vd), before the out projection."""
    B, S = c_kv.shape[:2]
    H = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvb = p["wkv_b"].astype(c_kv.dtype).reshape(cfg.kv_lora_rank, H, nope + vd)
    k_nope = jnp.einsum("bsc,chn->bshn", c_kv, kvb[..., :nope])
    v = jnp.einsum("bsc,chn->bshn", c_kv, kvb[..., nope:])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)

    q = shard(q.transpose(0, 2, 1, 3), "batch", "heads", "seq_q", None)
    k = shard(k.transpose(0, 2, 1, 3), "batch", "heads", None, None)
    v = shard(v.transpose(0, 2, 1, 3), "batch", "heads", None, None)
    out = blockwise_attention(q, k, v, qpos, qpos, block=block,
                              scale=mla_scale(cfg))
    return out.transpose(0, 2, 1, 3).reshape(B, S, H * vd)


def apply_mla_full(p, cfg, x, positions):
    dt = x.dtype
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, positions)
    qpos = positions[0] if positions.ndim == 2 else positions
    out = _mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope, qpos)
    return out @ p["wo"].astype(dt)


def _prefill_block(S: int, target: int = 512) -> int:
    """A key block near ``target`` that tiles S with the least padding."""
    n = -(-S // target)
    per = -(-S // n)
    return -(-per // 8) * 8


def apply_mla_prefill(p, cfg, x, positions, cache):
    """Causal MLA over every position of x that also writes its latent
    (``c_kv``) and rotated rope key (``k_rope``) into the cache from
    position 0: the cache ``apply_mla_decode`` leaves after S calls, up to
    rounding. One batch row attends at a time (its scores are H x S x
    block), so a long prompt batch fits beside the weights.

    Returns (out (B,S,D), new_cache)."""
    dt = x.dtype
    S = x.shape[1]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, positions)
    qpos = positions[0] if positions.ndim == 2 else positions
    block = _prefill_block(S)

    def row(xs):
        qn, qr, ck, kr = (a[None] for a in xs)
        return _mla_attend(p, cfg, qn, qr, ck, kr, qpos, block)[0]

    out = jax.lax.map(row, (q_nope, q_rope, c_kv, k_rope))
    c_c = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0))
    r_c = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope[:, :, 0, :].astype(cache["k_rope"].dtype),
        (0, 0, 0))
    c_c = shard(c_c, "batch", "kv_seq", None)
    r_c = shard(r_c, "batch", "kv_seq", None)
    return out @ p["wo"].astype(dt), {"c_kv": c_c, "k_rope": r_c}


def init_mla_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """MLA caches the COMPRESSED latent (this is the point of MLA)."""
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
    }


def apply_mla_decode(p, cfg, x, cache, index):
    """Absorbed-matmul MLA decode: attends in latent space, O(kv_lora) cache."""
    B = x.shape[0]
    H = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = x.dtype
    pos = jnp.full((B, 1), index, jnp.int32)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(p, cfg, x, pos)

    c_kv = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv_new.astype(cache["c_kv"].dtype), (0, index, 0))
    k_rope = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope_new[:, :, 0, :].astype(cache["k_rope"].dtype),
        (0, index, 0))
    c_kv = shard(c_kv, "batch", "kv_seq", None)
    k_rope = shard(k_rope, "batch", "kv_seq", None)

    kvb = p["wkv_b"].astype(dt).reshape(cfg.kv_lora_rank, H, nope + vd)
    w_uk, w_uv = kvb[..., :nope], kvb[..., nope:]
    # absorb W_uk into the query -> latent-space scores
    q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)          # (B,1,H,kvr)
    s = jnp.einsum("bshc,btc->bhst", q_lat.astype(jnp.float32),
                   c_kv.astype(jnp.float32))
    s += jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                    k_rope.astype(jnp.float32))
    s *= mla_scale(cfg)
    Smax = c_kv.shape[1]
    valid = jnp.arange(Smax)[None, None, None, :] <= index
    s = jnp.where(valid, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,btc->bshc", w.astype(c_kv.dtype), c_kv)  # latent ctx
    o = jnp.einsum("bshc,chn->bshn", ctx.astype(dt), w_uv)          # (B,1,H,vd)
    o = o.reshape(B, 1, H * vd)
    return o @ p["wo"].astype(dt), {"c_kv": c_kv, "k_rope": k_rope}
