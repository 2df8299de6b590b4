"""Model assembly for all six families (dense / moe / ssm / hybrid / encdec / vlm).

All layer stacks are ``lax.scan``-ed over stacked parameters (leading layer
axis) so the HLO stays small and compile time flat in depth — required for
the 61-layer / 671B dry-run. Remat policy ("none" | "dots" | "full") wraps
the scanned layer body.

``apply_lm``         : full-sequence forward -> (logits, aux)  [train]
``apply_lm_decode``  : one-token forward with caches -> (logits, new_caches)
``apply_lm_prefill`` : padded-prompt forward that writes the decode caches
                       -> (last-position logits, caches)  [dense, ssm, moe]
``apply_moe_prefill``: the same for moe, with each layer's rows per held
                       expert
``init_lm``/``init_caches`` build the matching parameter / cache pytrees.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.sharding.ctx import shard


def _dt(cfg):
    return L.dtype_of(cfg.param_dtype)


def _cdt(cfg):
    return L.dtype_of(cfg.compute_dtype)


def _remat(fn, mode: str):
    if mode == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


# ---------------------------------------------------------------------------
# per-family layer init
# ---------------------------------------------------------------------------

def _init_dense_layer(cfg, dtype):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {
            "ln1": L.init_rmsnorm(cfg.d_model, dtype),
            "attn": A.init_attention(k1, cfg, dtype=dtype),
            "ln2": L.init_rmsnorm(cfg.d_model, dtype),
            "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype),
        }
    return init


def _init_moe_layer(cfg, dtype):
    def init(key):
        k1, k2 = jax.random.split(key)
        attn = (A.init_mla(k1, cfg, dtype) if cfg.attention == "mla"
                else A.init_attention(k1, cfg, dtype=dtype))
        return {
            "ln1": L.init_rmsnorm(cfg.d_model, dtype),
            "attn": attn,
            "ln2": L.init_rmsnorm(cfg.d_model, dtype),
            "moe": M.init_moe(k2, cfg, dtype),
        }
    return init


def _init_moe_dense_layer(cfg, dtype):
    """DeepSeek first_k_dense layers: MLA attention + dense MLP."""
    def init(key):
        k1, k2 = jax.random.split(key)
        attn = (A.init_mla(k1, cfg, dtype) if cfg.attention == "mla"
                else A.init_attention(k1, cfg, dtype=dtype))
        return {
            "ln1": L.init_rmsnorm(cfg.d_model, dtype),
            "attn": attn,
            "ln2": L.init_rmsnorm(cfg.d_model, dtype),
            "mlp": L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype),
        }
    return init


def _init_ssm_layer(cfg, dtype):
    def init(key):
        return {"ln": L.init_rmsnorm(cfg.d_model, dtype),
                "ssm": S.init_ssm(key, cfg, dtype)}
    return init


def _init_shared_block(cfg, key, dtype):
    """Zamba2 shared attention block over concat(hidden, embed0) = 2*d_model."""
    k1, k2 = jax.random.split(key)
    Dc = 2 * cfg.d_model
    return {
        "ln1": L.init_rmsnorm(Dc, dtype),
        "attn": A.init_attention(k1, cfg, d_in=Dc, dtype=dtype),
        "ln2": L.init_rmsnorm(Dc, dtype),
        "mlp": {"gate": L.dense_init(jax.random.fold_in(k2, 0), Dc, cfg.d_ff, dtype),
                "up": L.dense_init(jax.random.fold_in(k2, 1), Dc, cfg.d_ff, dtype),
                "down": L.dense_init(jax.random.fold_in(k2, 2), cfg.d_ff, cfg.d_model, dtype)},
    }


def _init_encdec_dec_layer(cfg, dtype):
    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": L.init_rmsnorm(cfg.d_model, dtype),
            "self_attn": A.init_attention(k1, cfg, dtype=dtype),
            "ln_x": L.init_rmsnorm(cfg.d_model, dtype),
            "cross_attn": A.init_attention(k2, cfg, dtype=dtype),
            "ln2": L.init_rmsnorm(cfg.d_model, dtype),
            "mlp": L.init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act, dtype),
        }
    return init


# ---------------------------------------------------------------------------
# init_lm
# ---------------------------------------------------------------------------

def init_lm(key, cfg) -> Dict[str, Any]:
    dtype = _dt(cfg)
    V, D = cfg.padded_vocab, cfg.d_model
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": L.init_embed(ks[0], V, D, dtype),
                              "final_norm": L.init_rmsnorm(D, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": L.dense_init(ks[1], D, V, dtype)}

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = L.stack_init(_init_dense_layer(cfg, dtype), ks[2],
                                        cfg.num_layers)
    elif fam == "moe":
        n_moe = cfg.num_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            params["dense_layers"] = L.stack_init(
                _init_moe_dense_layer(cfg, dtype), ks[3], cfg.first_k_dense)
        params["layers"] = L.stack_init(_init_moe_layer(cfg, dtype), ks[2], n_moe)
        if cfg.mtp_depth:
            km = jax.random.split(ks[4], 3)
            params["mtp"] = {
                "proj": L.dense_init(km[0], 2 * D, D, dtype),
                "norm_h": L.init_rmsnorm(D, dtype),
                "norm_e": L.init_rmsnorm(D, dtype),
                "block": _init_dense_layer(
                    cfg.replace(d_ff=cfg.moe_d_ff * cfg.experts_per_token),
                    dtype)(km[1]),
            }
    elif fam == "ssm":
        params["layers"] = L.stack_init(_init_ssm_layer(cfg, dtype), ks[2],
                                        cfg.num_layers)
    elif fam == "hybrid":
        G = cfg.num_layers // cfg.shared_attn_interval
        leftover = cfg.num_layers - G * cfg.shared_attn_interval
        inner = _init_ssm_layer(cfg, dtype)

        def group_init(k):
            return L.stack_init(inner, k, cfg.shared_attn_interval)
        params["groups"] = L.stack_init(group_init, ks[2], G)
        if leftover:
            params["leftover"] = L.stack_init(inner, ks[5], leftover)
        params["shared"] = _init_shared_block(cfg, ks[6], dtype)
    elif fam == "encdec":
        params["enc_layers"] = L.stack_init(_init_dense_layer(cfg, dtype),
                                            ks[2], cfg.num_enc_layers)
        params["dec_layers"] = L.stack_init(_init_encdec_dec_layer(cfg, dtype),
                                            ks[3], cfg.num_layers)
        params["enc_norm"] = L.init_rmsnorm(D, dtype)
    else:
        raise ValueError(fam)
    return params


# ---------------------------------------------------------------------------
# full-sequence bodies
# ---------------------------------------------------------------------------

def _dense_body(cfg, lp, h, positions, prefix_len=None):
    h = h + A.apply_attention_full(lp["attn"], cfg,
                                   L.apply_rmsnorm(lp["ln1"], h, cfg.norm_eps),
                                   positions, prefix_len)
    h = h + L.apply_mlp(lp["mlp"], L.apply_rmsnorm(lp["ln2"], h, cfg.norm_eps),
                        cfg.act)
    return shard(h, "batch", None, None)


def _moe_dense_body(cfg, lp, h, positions):
    """DeepSeek first_k_dense layers: MLA (or GQA) attention + dense MLP."""
    attn_in = L.apply_rmsnorm(lp["ln1"], h, cfg.norm_eps)
    if cfg.attention == "mla":
        h = h + A.apply_mla_full(lp["attn"], cfg, attn_in, positions)
    else:
        h = h + A.apply_attention_full(lp["attn"], cfg, attn_in, positions)
    h = h + L.apply_mlp(lp["mlp"], L.apply_rmsnorm(lp["ln2"], h, cfg.norm_eps),
                        cfg.act)
    return shard(h, "batch", None, None)


def _moe_body(cfg, lp, h, positions):
    attn_in = L.apply_rmsnorm(lp["ln1"], h, cfg.norm_eps)
    if cfg.attention == "mla":
        h = h + A.apply_mla_full(lp["attn"], cfg, attn_in, positions)
    else:
        h = h + A.apply_attention_full(lp["attn"], cfg, attn_in, positions)
    y, aux = M.apply_moe(lp["moe"], cfg,
                         L.apply_rmsnorm(lp["ln2"], h, cfg.norm_eps))
    return shard(h + y, "batch", None, None), aux


def _ssm_body(cfg, lp, h):
    h = h + S.apply_ssm_full(lp["ssm"], cfg,
                             L.apply_rmsnorm(lp["ln"], h, cfg.norm_eps))
    return shard(h, "batch", None, None)


def _shared_body(cfg, sp, h, emb0, positions):
    c = jnp.concatenate([h, emb0], axis=-1)
    h = h + A.apply_attention_full(sp["attn"], cfg,
                                   L.apply_rmsnorm(sp["ln1"], c, cfg.norm_eps),
                                   positions)
    c2 = jnp.concatenate([h, emb0], axis=-1)
    m = L.apply_rmsnorm(sp["ln2"], c2, cfg.norm_eps)
    m = jax.nn.silu(m @ sp["mlp"]["gate"].astype(h.dtype)) * (m @ sp["mlp"]["up"].astype(h.dtype))
    return shard(h + m @ sp["mlp"]["down"].astype(h.dtype), "batch", None, None)


def _cross_attention(p, cfg, x, enc_out):
    """Full cross-attention (decoder queries over encoder keys)."""
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
    out = A.blockwise_attention(q, k, v, qpos, kpos, prefix_len=jnp.int32(1))
    out = out.transpose(0, 2, 1, 3).reshape(B, Sq, H * hd)
    return out @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# apply_lm (train / prefill)
# ---------------------------------------------------------------------------

def apply_lm(params, cfg, tokens, *, frames=None, patches=None,
             remat: str = "none") -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens: (B,S) int32. frames: (B,enc_S,D) [encdec]. patches: (B,P,D) [vlm].

    Returns (logits (B,S*,V), aux dict with 'moe_aux', optional 'mtp_logits').
    """
    cdt = _cdt(cfg)
    aux: Dict[str, Any] = {"moe_aux": jnp.zeros((), jnp.float32)}
    B, S = tokens.shape
    h = L.apply_embed({"table": params["embed"]["table"]}, tokens).astype(cdt)
    prefix_len = None

    if cfg.family == "vlm":
        h = jnp.concatenate([patches.astype(cdt), h], axis=1)
        prefix_len = jnp.int32(cfg.num_patches)
    h = shard(h, "batch", None, None)
    Stot = h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Stot, dtype=jnp.int32)[None], (B, Stot))

    fam = cfg.family
    if fam in ("dense", "vlm"):
        body = _remat(lambda hh, lp: (_dense_body(cfg, lp, hh, positions, prefix_len), None),
                      remat)
        h, _ = jax.lax.scan(lambda hh, lp: body(hh, lp), h, params["layers"])
    elif fam == "moe":
        if cfg.first_k_dense:
            dbody = _remat(
                lambda hh, lp: (_moe_dense_body(cfg, lp, hh, positions), None),
                remat)
            h, _ = jax.lax.scan(lambda hh, lp: dbody(hh, lp), h,
                                params["dense_layers"])

        def moe_step(carry, lp):
            hh, ax = carry
            hh, a = _moe_body(cfg, lp, hh, positions)
            return (hh, ax + a), None
        body = _remat(moe_step, remat)
        (h, moe_aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                       params["layers"])
        aux["moe_aux"] = moe_aux
        if cfg.mtp_depth and "mtp" in params:
            nxt = jnp.roll(tokens, -1, axis=1)
            e = L.apply_embed({"table": params["embed"]["table"]}, nxt).astype(cdt)
            m = jnp.concatenate([
                L.apply_rmsnorm(params["mtp"]["norm_h"], h, cfg.norm_eps),
                L.apply_rmsnorm(params["mtp"]["norm_e"], e, cfg.norm_eps)], -1)
            m = m @ params["mtp"]["proj"].astype(cdt)
            mcfg = cfg.replace(d_ff=cfg.moe_d_ff * cfg.experts_per_token)
            m = _dense_body(mcfg, params["mtp"]["block"], m, positions)
            m = L.apply_rmsnorm(params["final_norm"], m, cfg.norm_eps)
            aux["mtp_logits"] = _head(params, cfg, m)
    elif fam == "ssm":
        body = _remat(lambda hh, lp: (_ssm_body(cfg, lp, hh), None), remat)
        h, _ = jax.lax.scan(lambda hh, lp: body(hh, lp), h, params["layers"])
    elif fam == "hybrid":
        emb0 = h
        inner = _remat(lambda hh, lp: (_ssm_body(cfg, lp, hh), None), remat)

        def group_step(hh, gp):
            hh, _ = jax.lax.scan(lambda c, lp: inner(c, lp), hh, gp)
            hh = _shared_body(cfg, params["shared"], hh, emb0, positions)
            return hh, None
        h, _ = jax.lax.scan(group_step, h, params["groups"])
        if "leftover" in params:
            h, _ = jax.lax.scan(lambda c, lp: inner(c, lp), h, params["leftover"])
    elif fam == "encdec":
        he = frames.astype(cdt)
        Se = he.shape[1]
        epos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))
        ebody = _remat(
            lambda hh, lp: (_dense_body(cfg, lp, hh, epos, prefix_len=jnp.int32(Se)), None),
            remat)
        he, _ = jax.lax.scan(lambda hh, lp: ebody(hh, lp), he, params["enc_layers"])
        he = L.apply_rmsnorm(params["enc_norm"], he, cfg.norm_eps)

        def dec_body_fn(hh, lp):
            hh = hh + A.apply_attention_full(
                lp["self_attn"], cfg, L.apply_rmsnorm(lp["ln1"], hh, cfg.norm_eps),
                positions)
            hh = hh + _cross_attention(
                lp["cross_attn"], cfg, L.apply_rmsnorm(lp["ln_x"], hh, cfg.norm_eps), he)
            hh = hh + L.apply_mlp(lp["mlp"],
                                  L.apply_rmsnorm(lp["ln2"], hh, cfg.norm_eps), cfg.act)
            return shard(hh, "batch", None, None), None
        dbody = _remat(dec_body_fn, remat)
        h, _ = jax.lax.scan(lambda hh, lp: dbody(hh, lp), h, params["dec_layers"])
    else:
        raise ValueError(fam)

    h = L.apply_rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = _head(params, cfg, h)
    return logits, aux


def _head(params, cfg, h):
    if "lm_head" in params:
        logits = h @ params["lm_head"]["w"].astype(h.dtype)
    else:
        logits = h @ params["embed"]["table"].T.astype(h.dtype)
    return shard(logits.astype(jnp.float32), "batch", None, "vocab")


# ---------------------------------------------------------------------------
# caches + decode
# ---------------------------------------------------------------------------

def _stack_cache(make_one, n: int):
    one = make_one()
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)


def init_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"layers": _stack_cache(
            lambda: A.init_kv_cache(cfg, batch, max_len, dtype), cfg.num_layers)}
    if fam == "moe":
        n_moe = cfg.num_layers - cfg.first_k_dense
        mk = ((lambda: A.init_mla_cache(cfg, batch, max_len, dtype))
              if cfg.attention == "mla"
              else (lambda: A.init_kv_cache(cfg, batch, max_len, dtype)))
        c = {"layers": _stack_cache(mk, n_moe)}
        if cfg.first_k_dense:
            c["dense_layers"] = _stack_cache(mk, cfg.first_k_dense)
        return c
    if fam == "ssm":
        return {"layers": _stack_cache(
            lambda: S.init_ssm_cache(cfg, batch), cfg.num_layers)}
    if fam == "hybrid":
        G = cfg.num_layers // cfg.shared_attn_interval
        leftover = cfg.num_layers - G * cfg.shared_attn_interval
        c = {"groups": _stack_cache(
                lambda: _stack_cache(lambda: S.init_ssm_cache(cfg, batch),
                                     cfg.shared_attn_interval), G),
             "shared": _stack_cache(
                lambda: A.init_kv_cache(cfg, batch, max_len, dtype), G)}
        if leftover:
            c["leftover"] = _stack_cache(
                lambda: S.init_ssm_cache(cfg, batch), leftover)
        return c
    if fam == "encdec":
        return {"self": _stack_cache(
                    lambda: A.init_kv_cache(cfg, batch, max_len, dtype),
                    cfg.num_layers),
                "cross": _stack_cache(
                    lambda: A.init_kv_cache(cfg, batch, cfg.enc_seq, dtype),
                    cfg.num_layers)}
    raise ValueError(fam)


def _cross_attention_decode(p, cfg, x, kc, vc):
    B = x.shape[0]
    hd, H, KH = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(B, KH, H // KH, hd)
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * hd ** -0.5
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", w.astype(vc.dtype), vc)
    return o.reshape(B, 1, H * hd).astype(dt) @ p["wo"].astype(dt)


def apply_lm_decode(params, cfg, token, caches, index):
    """token: (B,1) int32; index: scalar int32 current position.

    Returns (logits (B,1,V), new_caches).
    """
    cdt = _cdt(cfg)
    B = token.shape[0]
    h = L.apply_embed({"table": params["embed"]["table"]}, token).astype(cdt)
    fam = cfg.family

    if fam in ("dense", "vlm"):
        def step(hh, xs):
            lp, cache = xs
            a, nc = A.apply_attention_decode(
                lp["attn"], cfg, L.apply_rmsnorm(lp["ln1"], hh, cfg.norm_eps),
                cache, index)
            hh = hh + a
            hh = hh + L.apply_mlp(lp["mlp"],
                                  L.apply_rmsnorm(lp["ln2"], hh, cfg.norm_eps),
                                  cfg.act)
            return hh, nc
        h, new = jax.lax.scan(step, h, (params["layers"], caches["layers"]))
        caches = {"layers": new}
    elif fam == "moe":
        dec = (A.apply_mla_decode if cfg.attention == "mla"
               else A.apply_attention_decode)
        new_caches = {}
        if cfg.first_k_dense:
            def dstep(hh, xs):
                lp, cache = xs
                a, nc = dec(lp["attn"], cfg,
                            L.apply_rmsnorm(lp["ln1"], hh, cfg.norm_eps),
                            cache, index)
                hh = hh + a
                hh = hh + L.apply_mlp(lp["mlp"],
                                      L.apply_rmsnorm(lp["ln2"], hh, cfg.norm_eps),
                                      cfg.act)
                return hh, nc
            h, newd = jax.lax.scan(dstep, h, (params["dense_layers"],
                                              caches["dense_layers"]))
            new_caches["dense_layers"] = newd

        def mstep(hh, xs):
            lp, cache = xs
            a, nc = dec(lp["attn"], cfg,
                        L.apply_rmsnorm(lp["ln1"], hh, cfg.norm_eps), cache, index)
            hh = hh + a
            y, _ = M.apply_moe(lp["moe"], cfg,
                               L.apply_rmsnorm(lp["ln2"], hh, cfg.norm_eps))
            return hh + y, nc
        h, newm = jax.lax.scan(mstep, h, (params["layers"], caches["layers"]))
        new_caches["layers"] = newm
        caches = new_caches
    elif fam == "ssm":
        def step(hh, xs):
            lp, cache = xs
            y, nc = S.apply_ssm_decode(
                lp["ssm"], cfg, L.apply_rmsnorm(lp["ln"], hh, cfg.norm_eps), cache)
            return hh + y, nc
        h, new = jax.lax.scan(step, h, (params["layers"], caches["layers"]))
        caches = {"layers": new}
    elif fam == "hybrid":
        emb0 = h

        def inner(hh, xs):
            lp, cache = xs
            y, nc = S.apply_ssm_decode(
                lp["ssm"], cfg, L.apply_rmsnorm(lp["ln"], hh, cfg.norm_eps), cache)
            return hh + y, nc

        def group_step(hh, xs):
            gp, gcache, scache = xs
            hh, ncache = jax.lax.scan(inner, hh, (gp, gcache))
            sp = params["shared"]
            c = jnp.concatenate([hh, emb0], axis=-1)
            a, nsc = A.apply_attention_decode(
                sp["attn"], cfg, L.apply_rmsnorm(sp["ln1"], c, cfg.norm_eps),
                scache, index)
            hh = hh + a
            c2 = jnp.concatenate([hh, emb0], axis=-1)
            m = L.apply_rmsnorm(sp["ln2"], c2, cfg.norm_eps)
            m = jax.nn.silu(m @ sp["mlp"]["gate"].astype(hh.dtype)) * (m @ sp["mlp"]["up"].astype(hh.dtype))
            hh = hh + m @ sp["mlp"]["down"].astype(hh.dtype)
            return hh, (ncache, nsc)
        h, (ng, ns) = jax.lax.scan(group_step, h,
                                   (params["groups"], caches["groups"],
                                    caches["shared"]))
        new = {"groups": ng, "shared": ns}
        if "leftover" in params:
            h, nl = jax.lax.scan(inner, h, (params["leftover"], caches["leftover"]))
            new["leftover"] = nl
        caches = new
    elif fam == "encdec":
        def step(hh, xs):
            lp, scache, xcache = xs
            a, nc = A.apply_attention_decode(
                lp["self_attn"], cfg,
                L.apply_rmsnorm(lp["ln1"], hh, cfg.norm_eps), scache, index)
            hh = hh + a
            hh = hh + _cross_attention_decode(
                lp["cross_attn"], cfg,
                L.apply_rmsnorm(lp["ln_x"], hh, cfg.norm_eps),
                xcache["k"], xcache["v"])
            hh = hh + L.apply_mlp(lp["mlp"],
                                  L.apply_rmsnorm(lp["ln2"], hh, cfg.norm_eps),
                                  cfg.act)
            return hh, nc
        h, new = jax.lax.scan(step, h, (params["dec_layers"], caches["self"],
                                        caches["cross"]))
        caches = {"self": new, "cross": caches["cross"]}
    else:
        raise ValueError(fam)

    h = L.apply_rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return _head(params, cfg, h), caches


# ---------------------------------------------------------------------------
# prefill: one call that writes the decode caches
# ---------------------------------------------------------------------------

# the families ``apply_lm_prefill`` covers; the others prefill through
# ``apply_lm_decode``, one call a prompt token
PREFILL_FAMILIES = ("dense", "ssm", "moe")


def prefill_len(cfg, max_len: int) -> int:
    """The padded prompt length of ``apply_lm_prefill`` for caches of
    ``max_len``: max_len itself, rounded up to a multiple of ``ssm_chunk``
    for SSM layers when it exceeds one chunk (``ssd_chunked``'s need)."""
    if cfg.family == "ssm" and max_len > cfg.ssm_chunk:
        return -(-max_len // cfg.ssm_chunk) * cfg.ssm_chunk
    return max_len


def apply_lm_prefill(params, cfg, tokens, length, caches):
    """tokens: (B,S) int32, the prompt right-padded to S = ``prefill_len``;
    length: scalar int32 (traced) true prompt length, 1..S; caches: as
    ``init_caches`` builds them.

    Returns (logits (B,1,V) at position length-1, the caches that
    ``length`` calls of ``apply_lm_decode`` leave, up to rounding). Causal
    masking keeps every position below ``length`` exact; cache entries at
    or past it hold padding, which decode overwrites before it reads them.
    """
    if cfg.family == "moe":
        return apply_moe_prefill(params, cfg, tokens, length, caches)[:2]
    cdt = _cdt(cfg)
    B, Sp = tokens.shape
    h = L.apply_embed({"table": params["embed"]["table"]}, tokens).astype(cdt)
    h = shard(h, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(Sp, dtype=jnp.int32)[None], (B, Sp))

    fam = cfg.family
    if fam == "dense":
        def step(hh, xs):
            lp, cache = xs
            a, nc = A.apply_attention_prefill(
                lp["attn"], cfg, L.apply_rmsnorm(lp["ln1"], hh, cfg.norm_eps),
                positions, cache)
            hh = hh + a
            hh = hh + L.apply_mlp(lp["mlp"],
                                  L.apply_rmsnorm(lp["ln2"], hh, cfg.norm_eps),
                                  cfg.act)
            return shard(hh, "batch", None, None), nc
    elif fam == "ssm":
        def step(hh, xs):
            lp, cache = xs
            y, nc = S.apply_ssm_prefill(
                lp["ssm"], cfg, L.apply_rmsnorm(lp["ln"], hh, cfg.norm_eps),
                length, cache)
            return shard(hh + y, "batch", None, None), nc
    else:
        raise ValueError(f"no one-call prefill for family {fam!r}")
    h, new = jax.lax.scan(step, h, (params["layers"], caches["layers"]))

    h = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
    h = L.apply_rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return _head(params, cfg, h), {"layers": new}


def apply_moe_prefill(params, cfg, tokens, length, caches):
    """``apply_lm_prefill`` for family moe (MLA or GQA attention): the
    leading dense layers, then the expert layers, each computing every
    (token, held expert) pair of the prompt's positions (padding is routed
    nowhere). Returns (logits (B,1,V), caches, rows per held expert of each
    expert layer (layers, experts held) int32)."""
    cdt = _cdt(cfg)
    B, Sp = tokens.shape
    h = L.apply_embed({"table": params["embed"]["table"]}, tokens).astype(cdt)
    h = shard(h, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(Sp, dtype=jnp.int32)[None], (B, Sp))
    valid = positions < length
    attend = (A.apply_mla_prefill if cfg.attention == "mla"
              else A.apply_attention_prefill)

    def attn(lp, hh, cache):
        a, nc = attend(lp["attn"], cfg,
                       L.apply_rmsnorm(lp["ln1"], hh, cfg.norm_eps),
                       positions, cache)
        return hh + a, nc

    new = {}
    if cfg.first_k_dense:
        def dstep(hh, xs):
            lp, cache = xs
            hh, nc = attn(lp, hh, cache)
            hh = hh + L.apply_mlp(lp["mlp"],
                                  L.apply_rmsnorm(lp["ln2"], hh, cfg.norm_eps),
                                  cfg.act)
            return shard(hh, "batch", None, None), nc
        h, new["dense_layers"] = jax.lax.scan(
            dstep, h, (params["dense_layers"], caches["dense_layers"]))

    def mstep(hh, xs):
        lp, cache = xs
        hh, nc = attn(lp, hh, cache)
        y, load = M.apply_moe_held(
            lp["moe"], cfg, L.apply_rmsnorm(lp["ln2"], hh, cfg.norm_eps), valid)
        return shard(hh + y, "batch", None, None), (nc, load)
    h, (new["layers"], load) = jax.lax.scan(
        mstep, h, (params["layers"], caches["layers"]))

    h = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
    h = L.apply_rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return _head(params, cfg, h), new, load
