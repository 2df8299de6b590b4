"""Model assembly for all six families (dense / moe / ssm / hybrid / encdec / vlm).

Each family's layer is written once and serves three modes (``_Mode``):
``full`` runs the whole sequence (train), ``prefill`` a right-padded prompt
that writes the decode caches, ``decode`` one token against them. The mode
maps a mixer call to its function (GQA or MLA attention, SSM,
cross-attention) and states which expert path an expert layer takes. The
blocks (``_mlp_block``, ``_moe_block``, ``_ssm_block``, ``_shared_block``,
``_dec_block``) take (mode, layer params, h, cache), and ``_walk`` runs a
family's layer stacks and their caches, the same for every mode.

All layer stacks are ``lax.scan``-ed over stacked parameters (leading layer
axis) so the HLO stays small and compile time flat in depth — required for
the 61-layer / 671B dry-run. In full mode the remat policy ("none" | "dots"
| "full") wraps the scanned layer body.

``apply_lm``         : full-sequence forward -> (logits, aux)  [train]
``apply_lm_prefill`` : padded-prompt forward that writes the decode caches
                       -> (last-position logits, caches, rows per held
                       expert of each expert layer or None)
                       [``PREFILL_FAMILIES``]
``apply_lm_decode``  : one-token forward with caches -> (logits, new_caches)
``init_lm``/``init_caches`` build the matching parameter / cache pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.sharding.ctx import shard


def _cdt(cfg):
    return L.dtype_of(cfg.compute_dtype)


def _remat(fn, mode: str):
    if mode == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def _mtp_cfg(cfg):
    """The MTP block's config: a GQA attention + MLP block as wide as the
    routed experts a token uses."""
    return cfg.replace(d_ff=cfg.moe_d_ff * cfg.experts_per_token,
                       attention="gqa")


# ---------------------------------------------------------------------------
# parameters and caches
# ---------------------------------------------------------------------------

def _init_block(cfg, dtype, ffn: str = "mlp"):
    """Attention (MLA or GQA by ``cfg.attention``) + an MLP or experts."""
    def init(key):
        k1, k2 = jax.random.split(key)
        attn = (A.init_mla(k1, cfg, dtype) if cfg.attention == "mla"
                else A.init_attention(k1, cfg, dtype=dtype))
        return {
            "ln1": L.init_rmsnorm(cfg.d_model, dtype),
            "attn": attn,
            "ln2": L.init_rmsnorm(cfg.d_model, dtype),
            ffn: (M.init_moe(k2, cfg, dtype) if ffn == "moe" else
                  L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype)),
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


def init_lm(key, cfg) -> Dict[str, Any]:
    dtype = L.dtype_of(cfg.param_dtype)
    V, D = cfg.padded_vocab, cfg.d_model
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": L.init_embed(ks[0], V, D, dtype),
                              "final_norm": L.init_rmsnorm(D, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": L.dense_init(ks[1], D, V, dtype)}

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = L.stack_init(_init_block(cfg, dtype), ks[2],
                                        cfg.num_layers)
    elif fam == "moe":
        n_moe = cfg.num_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            params["dense_layers"] = L.stack_init(
                _init_block(cfg, dtype), ks[3], cfg.first_k_dense)
        params["layers"] = L.stack_init(_init_block(cfg, dtype, "moe"), ks[2],
                                        n_moe)
        if cfg.mtp_depth:
            km = jax.random.split(ks[4], 3)
            params["mtp"] = {
                "proj": L.dense_init(km[0], 2 * D, D, dtype),
                "norm_h": L.init_rmsnorm(D, dtype),
                "norm_e": L.init_rmsnorm(D, dtype),
                "block": _init_block(_mtp_cfg(cfg), dtype)(km[1]),
            }
    elif fam == "ssm":
        params["layers"] = L.stack_init(_init_ssm_layer(cfg, dtype), ks[2],
                                        cfg.num_layers)
    elif fam == "hybrid":
        G, leftover = divmod(cfg.num_layers, cfg.shared_attn_interval)
        inner = _init_ssm_layer(cfg, dtype)

        def group_init(k):
            return L.stack_init(inner, k, cfg.shared_attn_interval)
        params["groups"] = L.stack_init(group_init, ks[2], G)
        if leftover:
            params["leftover"] = L.stack_init(inner, ks[5], leftover)
        params["shared"] = _init_shared_block(cfg, ks[6], dtype)
    elif fam == "encdec":
        params["enc_layers"] = L.stack_init(_init_block(cfg, dtype),
                                            ks[2], cfg.num_enc_layers)
        params["dec_layers"] = L.stack_init(_init_encdec_dec_layer(cfg, dtype),
                                            ks[3], cfg.num_layers)
        params["enc_norm"] = L.init_rmsnorm(D, dtype)
    else:
        raise ValueError(fam)
    return params


def _stack_cache(make_one, n: int):
    one = make_one()
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)


def init_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    init_attn = A.init_mla_cache if cfg.attention == "mla" else A.init_kv_cache
    kv = lambda: init_attn(cfg, batch, max_len, dtype)
    ssm = lambda: S.init_ssm_cache(cfg, batch)
    fam, n = cfg.family, cfg.num_layers
    if fam in ("dense", "vlm", "ssm"):
        return {"layers": _stack_cache(ssm if fam == "ssm" else kv, n)}
    if fam == "moe":
        c = {"layers": _stack_cache(kv, n - cfg.first_k_dense)}
        if cfg.first_k_dense:
            c["dense_layers"] = _stack_cache(kv, cfg.first_k_dense)
        return c
    if fam == "hybrid":
        G, leftover = divmod(n, cfg.shared_attn_interval)
        c = {"groups": _stack_cache(
                lambda: _stack_cache(ssm, cfg.shared_attn_interval), G),
             "shared": _stack_cache(kv, G)}
        if leftover:
            c["leftover"] = _stack_cache(ssm, leftover)
        return c
    if fam == "encdec":
        return {"self": _stack_cache(kv, n), "cross": _stack_cache(
            lambda: A.init_kv_cache(cfg, batch, cfg.enc_seq, dtype), n)}
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# modes: how a block runs its mixers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class _Mode:
    """``full``: the whole sequence at ``positions`` (the first
    ``prefix_len`` attend both ways), no caches. ``prefill``: a prompt
    right-padded past ``length``, at ``positions``, writing the caches.
    ``decode``: one token at position ``index``, reading and writing them.
    Each mixer returns (y, new cache), the cache None in full mode."""
    cfg: Any
    kind: str
    positions: Any = None
    prefix_len: Any = None
    length: Any = None
    index: Any = None

    def attend(self, p, x, cache):
        """Self-attention: MLA or GQA by ``cfg.attention``."""
        cfg, mla = self.cfg, self.cfg.attention == "mla"
        if self.kind == "full":
            if mla:
                return A.apply_mla_full(p, cfg, x, self.positions), None
            return A.apply_attention_full(p, cfg, x, self.positions,
                                          self.prefix_len), None
        if self.kind == "prefill":
            fn = A.apply_mla_prefill if mla else A.apply_attention_prefill
            return fn(p, cfg, x, self.positions, cache)
        fn = A.apply_mla_decode if mla else A.apply_attention_decode
        return fn(p, cfg, x, cache, self.index)

    def ssm(self, p, x, cache):
        if self.kind == "full":
            return S.apply_ssm_full(p, self.cfg, x), None
        if self.kind == "prefill":
            return S.apply_ssm_prefill(p, self.cfg, x, self.length, cache)
        return S.apply_ssm_decode(p, self.cfg, x, cache)

    def cross(self, p, x, src):
        """Cross-attention over the encoder: src is its output in full
        mode, the layer's K/V cache of it in decode."""
        if self.kind == "full":
            return A.apply_cross_attention_full(p, self.cfg, x, src)
        return A.apply_cross_attention_decode(p, self.cfg, x, src)

    def expert_path(self):
        """(held, valid). held: the expert layers take the held path
        (``M.apply_moe_held_at``: every (token, held expert) pair, the
        experts read in place, rows per held expert returned), not
        ``M.apply_moe``. Full mode: ``apply_moe`` (with its aux loss).
        Prefill: the held path at every ``experts_held``, padding (valid
        False) routed nowhere. Decode: the held path where the layers hold
        a share of the experts, else ``apply_moe``."""
        if self.kind == "full":
            return False, None
        if self.kind == "prefill":
            return True, self.positions < self.length
        return bool(self.cfg.experts_held), None

    def out(self, h):
        """A block's output: batch-sharded, except in decode."""
        return h if self.kind == "decode" else shard(h, "batch", None, None)


def _norm(mode, p, x):
    return L.apply_rmsnorm(p, x, mode.cfg.norm_eps)


# ---------------------------------------------------------------------------
# blocks: one per layer kind, every mode
# ---------------------------------------------------------------------------

def _mlp_block(mode, lp, h, cache):
    """Attention + MLP: dense, vlm, moe's leading dense layers, the MTP
    block and the encoder."""
    a, cache = mode.attend(lp["attn"], _norm(mode, lp["ln1"], h), cache)
    h = h + a
    h = h + L.apply_mlp(lp["mlp"], _norm(mode, lp["ln2"], h), mode.cfg.act)
    return mode.out(h), cache


def _moe_block(mode, lp, h, cache, experts=None, layer=None, valid=None):
    """Attention + experts -> (h, cache, extra): ``M.apply_moe``'s aux
    loss, or with ``experts`` (every expert layer's held experts) the held
    path's rows per held expert of layer ``layer``."""
    a, cache = mode.attend(lp["attn"], _norm(mode, lp["ln1"], h), cache)
    h = h + a
    x = _norm(mode, lp["ln2"], h)
    if experts is None:
        y, extra = M.apply_moe(lp["moe"], mode.cfg, x)
    else:
        y, extra = M.apply_moe_held_at(lp["moe"], mode.cfg, x, experts,
                                       layer, valid)
    return mode.out(h + y), cache, extra


def _ssm_block(mode, lp, h, cache):
    y, cache = mode.ssm(lp["ssm"], _norm(mode, lp["ln"], h), cache)
    return mode.out(h + y), cache


def _shared_block(mode, sp, h, emb0, cache):
    """Zamba2's shared attention + MLP over concat(h, embed0)."""
    a, cache = mode.attend(
        sp["attn"], _norm(mode, sp["ln1"], jnp.concatenate([h, emb0], axis=-1)),
        cache)
    h = h + a
    m = _norm(mode, sp["ln2"], jnp.concatenate([h, emb0], axis=-1))
    m = jax.nn.silu(m @ sp["mlp"]["gate"].astype(h.dtype)) * (m @ sp["mlp"]["up"].astype(h.dtype))
    return mode.out(h + m @ sp["mlp"]["down"].astype(h.dtype)), cache


def _dec_block(mode, lp, h, cache):
    """The encdec decoder layer. cache: (self-attention cache, the
    ``mode.cross`` source)."""
    self_cache, src = cache
    a, self_cache = mode.attend(lp["self_attn"], _norm(mode, lp["ln1"], h),
                                self_cache)
    h = h + a
    h = h + mode.cross(lp["cross_attn"], _norm(mode, lp["ln_x"], h), src)
    h = h + L.apply_mlp(lp["mlp"], _norm(mode, lp["ln2"], h), mode.cfg.act)
    return mode.out(h), self_cache


# ---------------------------------------------------------------------------
# the layer-stack walk: one per family, every mode
# ---------------------------------------------------------------------------

def _scan(mode, block, h, params, caches, remat="none"):
    """``block(mode, layer params, h, cache) -> (h, new cache)`` over
    stacked layers: in full mode over the params alone, under ``remat``;
    else over (params, caches)."""
    if mode.kind == "full":
        return jax.lax.scan(
            _remat(lambda hh, lp: block(mode, lp, hh, None), remat), h, params)
    return jax.lax.scan(lambda hh, xs: block(mode, xs[0], hh, xs[1]), h,
                        (params, caches))


def _split_experts(layers):
    """An expert layer scan's per-layer params without the held experts,
    and the held experts' (layers, experts held, ...) stack. The scan
    slices no expert: the held path reads one in place by (layer, expert)
    where a tile of its rows uses it."""
    moe = dict(layers["moe"])
    experts = moe.pop("experts")
    return dict(layers, moe=moe), experts


def _moe_walk(mode, params, h, caches, remat):
    cfg = mode.cfg
    held, valid = mode.expert_path()
    new = {}
    if cfg.first_k_dense:
        h, new["dense_layers"] = _scan(mode, _mlp_block, h,
                                       params["dense_layers"],
                                       caches.get("dense_layers"), remat)
    if mode.kind == "full":
        def step(carry, lp):
            hh, ax = carry
            hh, _, a = _moe_block(mode, lp, hh, None)
            return (hh, ax + a), None
        (h, aux), _ = jax.lax.scan(_remat(step, remat),
                                   (h, jnp.zeros((), jnp.float32)),
                                   params["layers"])
        return h, new, aux

    # the all-experts path consumes every expert of its layer: it keeps
    # them in the scan's xs
    layers, experts = (_split_experts(params["layers"]) if held
                       else (params["layers"], None))

    def step(hh, xs):
        lp, cache, layer = xs
        hh, cache, rows = _moe_block(mode, lp, hh, cache, experts, layer,
                                     valid)
        return hh, (cache, rows if held else None)
    n_moe = cfg.num_layers - cfg.first_k_dense
    h, (new["layers"], rows) = jax.lax.scan(
        step, h, (layers, caches["layers"], jnp.arange(n_moe)))
    return h, new, rows


def _walk(mode, params, h, caches, remat="none", enc_out=None):
    """Run the family's layer stacks over h. caches: as ``init_caches``
    builds them ({} in full mode); enc_out: the encoder's output (encdec,
    full mode). Returns (h, new caches, extra): family moe's summed aux
    loss in full mode, or its rows per held expert of each expert layer
    (expert layers, experts held) on the held path; else None."""
    fam = mode.cfg.family
    if fam in ("dense", "vlm", "ssm"):
        block = _ssm_block if fam == "ssm" else _mlp_block
        h, new = _scan(mode, block, h, params["layers"], caches.get("layers"),
                       remat)
        return h, {"layers": new}, None
    if fam == "moe":
        return _moe_walk(mode, params, h, caches, remat)
    if fam == "hybrid":
        emb0 = h

        def group(mode, gp, hh, cache):
            ssm_cache, shared_cache = cache or (None, None)
            hh, ssm_cache = _scan(mode, _ssm_block, hh, gp, ssm_cache, remat)
            hh, shared_cache = _shared_block(mode, params["shared"], hh, emb0,
                                             shared_cache)
            return hh, (ssm_cache, shared_cache)
        h, (groups, shared) = _scan(
            mode, group, h, params["groups"],
            (caches.get("groups"), caches.get("shared")))
        new = {"groups": groups, "shared": shared}
        if "leftover" in params:
            h, new["leftover"] = _scan(mode, _ssm_block, h, params["leftover"],
                                       caches.get("leftover"), remat)
        return h, new, None
    if fam == "encdec":
        def dec(mode, lp, hh, cache):
            return _dec_block(mode, lp, hh, cache or (None, enc_out))
        h, new = _scan(mode, dec, h, params["dec_layers"],
                       (caches.get("self"), caches.get("cross")), remat)
        return h, {"self": new, "cross": caches.get("cross")}, None
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# entry points: train, prefill, decode
# ---------------------------------------------------------------------------

def _embed(params, cfg, tokens):
    return L.apply_embed({"table": params["embed"]["table"]},
                         tokens).astype(_cdt(cfg))


def _positions(B: int, S: int):
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def _head(params, cfg, h):
    h = L.apply_rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if "lm_head" in params:
        logits = h @ params["lm_head"]["w"].astype(h.dtype)
    else:
        logits = h @ params["embed"]["table"].T.astype(h.dtype)
    return shard(logits.astype(jnp.float32), "batch", None, "vocab")


def _encode(params, cfg, frames, remat="none"):
    """The encdec encoder over frames (B, enc_S, D), every position
    attending to every other."""
    he = frames.astype(_cdt(cfg))
    B, Se = he.shape[:2]
    mode = _Mode(cfg, "full", positions=_positions(B, Se),
                 prefix_len=jnp.int32(Se))
    he, _ = _scan(mode, _mlp_block, he, params["enc_layers"], None, remat)
    return L.apply_rmsnorm(params["enc_norm"], he, cfg.norm_eps)


def apply_lm(params, cfg, tokens, *, frames=None, patches=None,
             remat: str = "none") -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens: (B,S) int32. frames: (B,enc_S,D) [encdec]. patches: (B,P,D) [vlm].

    Returns (logits (B,S*,V), aux dict with 'moe_aux', optional 'mtp_logits').
    """
    aux: Dict[str, Any] = {"moe_aux": jnp.zeros((), jnp.float32)}
    B = tokens.shape[0]
    h = _embed(params, cfg, tokens)
    prefix_len = None
    if cfg.family == "vlm":
        h = jnp.concatenate([patches.astype(_cdt(cfg)), h], axis=1)
        prefix_len = jnp.int32(cfg.num_patches)
    h = shard(h, "batch", None, None)
    positions = _positions(B, h.shape[1])
    enc_out = (_encode(params, cfg, frames, remat) if cfg.family == "encdec"
               else None)
    mode = _Mode(cfg, "full", positions=positions, prefix_len=prefix_len)
    h, _, moe_aux = _walk(mode, params, h, {}, remat, enc_out)
    if moe_aux is not None:
        aux["moe_aux"] = moe_aux
    if cfg.mtp_depth and "mtp" in params:
        mtp = params["mtp"]
        e = _embed(params, cfg, jnp.roll(tokens, -1, axis=1))
        m = jnp.concatenate([_norm(mode, mtp["norm_h"], h),
                             _norm(mode, mtp["norm_e"], e)], -1)
        m = m @ mtp["proj"].astype(_cdt(cfg))
        m, _ = _mlp_block(_Mode(_mtp_cfg(cfg), "full", positions=positions),
                          mtp["block"], m, None)
        aux["mtp_logits"] = _head(params, cfg, m)
    return _head(params, cfg, h), aux


# the families ``apply_lm_prefill`` covers; the others prefill through
# ``apply_lm_decode``, one call a prompt token
PREFILL_FAMILIES = ("dense", "ssm", "moe")


def has_one_call_prefill(cfg) -> bool:
    """Whether ``apply_lm_prefill`` serves cfg's family."""
    return cfg.family in PREFILL_FAMILIES


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
    ``length`` calls of ``apply_lm_decode`` leave, up to rounding, and for
    family moe the rows per held expert of each expert layer (expert
    layers, experts held) int32, else None). Causal masking keeps every
    position below ``length`` exact; cache entries at or past it hold
    padding, which decode overwrites before it reads them. Expert layers
    compute every (token, held expert) pair of the prompt's positions
    (padding is routed nowhere).
    """
    if not has_one_call_prefill(cfg):
        raise ValueError(f"no one-call prefill for family {cfg.family!r}")
    B, Sp = tokens.shape
    h = shard(_embed(params, cfg, tokens), "batch", None, None)
    mode = _Mode(cfg, "prefill", positions=_positions(B, Sp), length=length)
    h, caches, rows = _walk(mode, params, h, caches)
    h = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
    return _head(params, cfg, h), caches, rows


def apply_moe_prefill(params, cfg, tokens, length, caches):
    """``apply_lm_prefill`` of a moe config: its rows are never None."""
    return apply_lm_prefill(params, cfg, tokens, length, caches)


def _decode(params, cfg, token, caches, index):
    """``apply_lm_decode`` with the walk's third output: family moe's rows
    per held expert of each expert layer on the held path, else None."""
    h = _embed(params, cfg, token)
    h, caches, rows = _walk(_Mode(cfg, "decode", index=index), params, h,
                            caches)
    return _head(params, cfg, h), caches, rows


def apply_lm_decode(params, cfg, token, caches, index):
    """token: (B,1) int32; index: scalar int32 current position.

    Returns (logits (B,1,V), new_caches).
    """
    return _decode(params, cfg, token, caches, index)[:2]
