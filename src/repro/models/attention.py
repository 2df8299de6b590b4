"""Attention: GQA/MHA and MLA (DeepSeek latent), full + prefill + decode
paths, and encoder-decoder cross-attention (full + decode).

Full-sequence attention is *blockwise* (lax.scan over KV blocks with online
softmax — flash-attention semantics at the XLA level) so that 32k-token
prefill never materializes the (S x S) score matrix. The per-block body is
wrapped in ``jax.checkpoint`` so the autodiff backward recomputes block
scores instead of saving O(S^2) residuals. The GQA prefill path is the
full path that also writes the prompt's rotated keys and values into the
decode cache; the MLA prefill writes the latent cache (``c_kv``, ``k_rope``)
and attends one batch row at a time, so its scores stay one row's size.

Decode attends a single new token against a KV cache laid out
(batch, kv_heads / f, seq, f * head_dim): f heads side by side in a row
(``kv_pack``), so a row fills a TPU tile's 128 lanes where a head is
narrower and the cache is neither padded nor laid out position-minor. The
sharding resolver prefers head-sharding and falls back to split-KV
sequence sharding when the head axis does not divide TP (flash-decoding
pattern). A decode call reads the cache where it lies and returns only the
new position's rows; the layer walk writes every layer's rows at once
(``write_rows``) into the caches the serving engine's decode step takes
donated and consumes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding.ctx import shard

KV_BLOCK = 1024
LANES = 128         # the minor dimension of a TPU tile


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
    position of x into the cache (``init_kv_cache``) from position 0: the
    cache ``apply_attention_decode`` leaves after S calls, up to rounding.

    Returns (out (B,S,D), new_cache)."""
    q, k, v = _qkv(p, cfg, x, positions)
    out = _attend(p, q, k, v, positions, None)
    f = kv_pack(cfg)
    k_c = jax.lax.dynamic_update_slice(
        cache["k"], _pack(k, f).astype(cache["k"].dtype), (0, 0, 0, 0))
    v_c = jax.lax.dynamic_update_slice(
        cache["v"], _pack(v, f).astype(cache["v"].dtype), (0, 0, 0, 0))
    k_c = shard(k_c, "batch", "kv_heads", "kv_seq", None)
    v_c = shard(v_c, "batch", "kv_heads", "kv_seq", None)
    return out, {"k": k_c, "v": v_c}


def kv_pack(cfg) -> int:
    """f, the kv heads a cache row holds side by side: the most that divide
    num_kv_heads and fit one tile's lanes (1 for heads of 128 or wider). A
    narrower head alone in a row leaves the cache padded to the lanes, or
    laid out position-minor, where a decode call's one-position rows are
    padded instead."""
    KH, hd = cfg.num_kv_heads, cfg.head_dim
    return max(f for f in range(1, KH + 1)
               if KH % f == 0 and (f == 1 or f * hd <= LANES))


def _pack(x, f):
    """(B,S,KH,hd) -> the cache's layout (B,KH/f,S,f*hd): kv heads j*f to
    j*f+f-1 side by side in row j."""
    B, S, KH, hd = x.shape
    return x.reshape(B, S, KH // f, f * hd).transpose(0, 2, 1, 3)


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Zero K/V caches (batch, kv_heads/f, max_len, f*head_dim), f
    ``kv_pack(cfg)``: the rotated keys and values of every position."""
    f, KH, hd = kv_pack(cfg), cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, KH // f, max_len, f * hd), dtype),
        "v": jnp.zeros((batch, KH // f, max_len, f * hd), dtype),
    }


def init_cross_cache(cfg, batch: int, enc_len: int, dtype=jnp.bfloat16):
    """Zero encoder K/V caches (batch, kv_heads, enc_len, head_dim), which
    ``apply_cross_attention_decode`` reads."""
    hd, KH = cfg.head_dim, cfg.num_kv_heads
    return {
        "k": jnp.zeros((batch, KH, enc_len, hd), dtype),
        "v": jnp.zeros((batch, KH, enc_len, hd), dtype),
    }


def apply_attention_decode(p, cfg, x, cache, index):
    """x: (B,1,D_in); cache k/v as ``init_kv_cache`` lays them out, read
    where they lie; index: scalar current position.

    Attends over the cache's positions below ``index`` and the new token's
    own key and value, which the cache does not hold yet, in f32. A query
    meets a packed row with zeros outside its own head's lanes, and keeps
    those lanes of the weighted sum of value rows. Returns (out (B,1,D),
    the new position's rows {"k", "v"} (B,KH/f,1,f*hd) in the cache's
    dtype): the walk writes them at ``index`` (``write_rows``).
    """
    B = x.shape[0]
    hd, H, KH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    f, G = kv_pack(cfg), H // KH
    dt, f32 = x.dtype, jnp.float32
    q = (x @ p["wq"].astype(dt)).reshape(B, 1, H, hd)
    k = (x @ p["wk"].astype(dt)).reshape(B, 1, KH, hd)
    v = (x @ p["wv"].astype(dt)).reshape(B, 1, KH, hd)
    pos = jnp.full((B, 1), index, jnp.int32)
    q = L.apply_rope(q, pos, cfg.rope_theta)
    k = L.apply_rope(k, pos, cfg.rope_theta)
    rows = {"k": _pack(k, f).astype(cache["k"].dtype),
            "v": _pack(v, f).astype(cache["v"].dtype)}
    k_c = shard(cache["k"], "batch", "kv_heads", "kv_seq", None)
    v_c = shard(cache["v"], "batch", "kv_heads", "kv_seq", None)

    # lane l of a row is head l // hd of its f: (f, 1, f*hd)
    own = jnp.arange(f * hd) // hd == jnp.arange(f)[:, None, None]
    qg = q.reshape(B, KH // f, f, G, hd).astype(f32)
    qg = jnp.where(own, jnp.tile(qg, f), 0.0)            # (B,KH/f,f,G,f*hd)
    k_new, v_new = (r[:, :, None].astype(f32) for r in (rows["k"], rows["v"]))
    s = jnp.sum(qg[..., None, :] * k_c[:, :, None, None].astype(f32), -1)
    s_new = jnp.sum(qg * k_new, -1)
    w, w_new = _softmax_with_new(s * hd ** -0.5, s_new * hd ** -0.5, index)
    o = jnp.sum(w[..., None] * v_c[:, :, None, None].astype(f32), -2)
    o = o + w_new[..., None] * v_new                     # (B,KH/f,f,G,f*hd)
    o = jnp.where(own, o, 0.0).reshape(B, KH // f, f, G, f, hd).sum(-2)
    o = o.reshape(B, 1, H * hd).astype(dt)
    return o @ p["wo"].astype(dt), rows


def _softmax_with_new(s, s_new, index):
    """The masked softmax of a decode step over the cache's scores s
    (..., S) at the positions below ``index`` and the new token's score
    s_new (...): the weights of the cache's positions (..., S) and of the
    new token (...)."""
    s = jnp.where(jnp.arange(s.shape[-1]) < index, s, -1e30)
    m = jnp.maximum(jnp.max(s, axis=-1), s_new)
    e, e_new = jnp.exp(s - m[..., None]), jnp.exp(s_new - m)
    total = jnp.sum(e, axis=-1) + e_new
    return e / total[..., None], e_new / total


def write_rows(cache, rows, index):
    """Write a decode step's new rows into attention caches at position
    ``index``: one ``dynamic_update_slice`` a tensor, each tensor's
    sequence axis its second last (GQA (..., B, KH/f, S, f*hd), MLA (...,
    B, S, c)), so a stack of layers takes every layer's row at once."""
    return jax.tree.map(
        lambda c, r: jax.lax.dynamic_update_slice_in_dim(c, r, index,
                                                         c.ndim - 2),
        cache, rows)


def apply_cross_attention_full(p, cfg, x, enc_out):
    """Decoder queries x (B,Sq,D) over every encoder position of enc_out
    (B,Se,D), no rope."""
    B, Sq, _ = x.shape
    Se = enc_out.shape[1]
    hd, H, KH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, Sq, H, hd).transpose(0, 2, 1, 3)
    k = (enc_out @ p["wk"].astype(dt)).reshape(B, Se, KH, hd)
    v = (enc_out @ p["wv"].astype(dt)).reshape(B, Se, KH, hd)
    if KH != H:
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    qpos = jnp.zeros((Sq,), jnp.int32)
    kpos = jnp.zeros((Se,), jnp.int32)
    out = blockwise_attention(q, k, v, qpos, kpos, prefix_len=jnp.int32(1))
    out = out.transpose(0, 2, 1, 3).reshape(B, Sq, H * hd)
    return out @ p["wo"].astype(dt)


def apply_cross_attention_decode(p, cfg, x, cache):
    """One decoder token x (B,1,D) over the encoder's K/V cache (B,KH,Se,hd)."""
    B = x.shape[0]
    hd, H, KH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype
    kc, vc = cache["k"], cache["v"]
    q = (x @ p["wq"].astype(dt)).reshape(B, KH, H // KH, hd)
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * hd ** -0.5
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", w.astype(vc.dtype), vc)
    return o.reshape(B, 1, H * hd).astype(dt) @ p["wo"].astype(dt)

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
    """Absorbed-matmul MLA decode: attends in latent space over the cache
    (``c_kv`` (B,S,kvr), ``k_rope`` (B,S,rope), read where it lies) at the
    positions below ``index`` and the new token's own latent and rope key.
    Returns (out (B,1,D), the new position's rows {"c_kv", "k_rope"}
    (B,1,kvr), (B,1,rope) in the cache's dtype): the walk writes them at
    ``index`` (``write_rows``)."""
    B = x.shape[0]
    H = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = x.dtype
    pos = jnp.full((B, 1), index, jnp.int32)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(p, cfg, x, pos)
    rows = {"c_kv": c_kv_new.astype(cache["c_kv"].dtype),
            "k_rope": k_rope_new[:, :, 0, :].astype(cache["k_rope"].dtype)}
    c_kv = shard(cache["c_kv"], "batch", "kv_seq", None)
    k_rope = shard(cache["k_rope"], "batch", "kv_seq", None)

    kvb = p["wkv_b"].astype(dt).reshape(cfg.kv_lora_rank, H, nope + vd)
    w_uk, w_uv = kvb[..., :nope], kvb[..., nope:]
    # absorb W_uk into the query -> latent-space scores
    q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)          # (B,1,H,kvr)
    q_lat, q_rope = q_lat.astype(jnp.float32), q_rope.astype(jnp.float32)
    c_new = rows["c_kv"].astype(jnp.float32)
    s = jnp.einsum("bshc,btc->bhst", q_lat, c_kv.astype(jnp.float32))
    s += jnp.einsum("bshr,btr->bhst", q_rope, k_rope.astype(jnp.float32))
    s_new = jnp.einsum("bshc,bsc->bhs", q_lat, c_new)
    s_new += jnp.einsum("bshr,bsr->bhs", q_rope,
                        rows["k_rope"].astype(jnp.float32))
    w, w_new = _softmax_with_new(s * mla_scale(cfg), s_new * mla_scale(cfg),
                                 index)
    ctx = jnp.einsum("bhst,btc->bshc", w.astype(c_kv.dtype), c_kv)  # latent ctx
    ctx = ctx + jnp.einsum("bhs,bsc->bshc", w_new.astype(c_kv.dtype),
                           rows["c_kv"])
    o = jnp.einsum("bshc,chn->bshn", ctx.astype(dt), w_uv)          # (B,1,H,vd)
    o = o.reshape(B, 1, H * vd)
    return o @ p["wo"].astype(dt), rows
