"""Mixture-of-Experts with expert parallelism.

TPU adaptation: instead of the CUDA-style token-permutation
or the classic GShard one-hot dispatch einsum — whose (tokens x E x capacity)
one-hot tensors add O(tokens^2) *fake* FLOPs and O(GB) temporaries — we use a
**sort-based capacity-bucketed dispatch**: tokens are argsorted by expert id,
ranked within their expert, and scattered into an (E_local, C, D) VMEM-friendly
buffer; expert matmuls are a single dense (E,C,D)x(E,D,F) einsum (MXU-aligned);
the combine is a scatter-add. Zero matmul FLOPs are spent on dispatch.

Expert parallelism runs under ``shard_map``: activations arrive replicated
across the ``model`` axis (standard TP layout), each shard computes its
E/TP experts over the full local batch, and partial outputs are ``psum``-ed
over ``model``. (The §Perf hillclimb replaces replicated activations + psum
with sequence-sharded activations + all-to-all dispatch: ``moe_a2a``.)

FSDP-compatible: if expert weights arrive d_model-sharded over ``data``
(DeepSeek-671B config), they are all-gathered per layer inside the shard_map
— exactly the FSDP weight-gather pattern.

A layer told that it holds ``experts_held`` experts from ``expert_offset``
(one chip's share under expert parallelism) routes over all
``num_experts`` and computes only its own experts' part of the result, for
every (token, held expert) pair: no capacity drop at any load. Its pairs
are sorted by expert and each expert runs as many row tiles as it has
rows, so the cost follows the routed rows and an expert that got no token
is never read. That path is for inference (its loops have a trip count
known only on the device, which reverse-mode differentiation refuses).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.sharding.ctx import axis_ctx, current_strategy, shard


def held_experts(cfg) -> Tuple[int, int]:
    """(first expert, count) of the experts this layer holds."""
    if cfg.experts_held:
        return cfg.expert_offset, cfg.experts_held
    return 0, cfg.num_experts


def init_moe(key, cfg, dtype=jnp.float32):
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    e0, n = held_experts(cfg)
    ks = jax.random.split(key, 6)
    # every expert's key is drawn, so a held expert's weights are those it
    # has in the whole layer
    held = lambda k: jax.random.split(k, E)[e0:e0 + n]
    p = {
        "router": L.dense_init(ks[0], D, E, jnp.float32),  # router kept fp32
        "experts": {
            "gate": jax.vmap(lambda k: L.dense_init(k, D, F, dtype))(held(ks[1])),
            "up": jax.vmap(lambda k: L.dense_init(k, D, F, dtype))(held(ks[2])),
            "down": jax.vmap(lambda k: L.dense_init(k, F, D, dtype))(held(ks[3])),
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = L.init_mlp(ks[4], D, F * cfg.num_shared_experts, "swiglu", dtype)
    if cfg.router_type == "noaux_tc":
        # e_score_correction_bias: moves selection, not the weights
        p["router_bias"] = jax.random.normal(ks[5], (E,), jnp.float32) \
            * cfg.router_bias_scale
    return p


def _noaux_tc(p, cfg, logits):
    """DeepSeek-V3's routing: sigmoid scores; selection by score + bias
    within the ``topk_group`` groups whose two best biased scores sum
    highest; weights the chosen experts' unbiased scores, normalised, times
    ``routed_scaling_factor``."""
    E, G, k = cfg.num_experts, cfg.n_group, cfg.experts_per_token
    scores = jax.nn.sigmoid(logits)
    biased = scores + p["router_bias"]
    if G > 1:
        grp = biased.reshape(biased.shape[:-1] + (G, E // G))
        best = jnp.sum(jax.lax.top_k(grp, min(2, E // G))[0], -1)   # (..,G)
        _, gidx = jax.lax.top_k(best, cfg.topk_group)
        keep = jnp.sum(jax.nn.one_hot(gidx, G, dtype=jnp.int32), -2) > 0
        biased = jnp.where(jnp.repeat(keep, E // G, axis=-1), biased,
                           -jnp.inf)
    _, idx = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, idx


def _route(p, cfg, x):
    """Returns (weights (B,S,k), idx (B,S,k), aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ p["router"])          # (B,S,E)
    if cfg.router_type == "noaux_tc":                       # deepseek-v3
        w, idx = _noaux_tc(p, cfg, logits)
    elif cfg.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(scores, cfg.experts_per_token)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    # switch-style load-balance aux loss
    probs = jax.nn.softmax(logits, axis=-1)
    E = cfg.num_experts
    one_hot = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
    f = jnp.mean(one_hot, axis=(0, 1))
    pbar = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * pbar) * cfg.aux_loss_coef
    return w.astype(x.dtype), idx, aux


def _held_tile(tokens: int, k: int, num_experts: int) -> int:
    """Rows of one tile of the held-expert path: an expert's expected rows
    and a quarter more, 8-aligned (an expert with more runs more tiles)."""
    return max(8, -(-math.ceil(1.25 * tokens * k / num_experts) // 8) * 8)


def _held_expert_compute(x2d, idx2d, w2d, valid, ex, e0, tile):
    """Every (token, held expert) pair of experts [e0, e0 + El), none
    dropped. x2d: (T, D); idx2d/w2d: (T, k); valid: (T,) bool or None
    (False: a padding row, routed nowhere); ex: gate/up (El, D, F), down
    (El, F, D). Returns ((T, D) float32 partial output, (El,) int32 rows
    each held expert computed)."""
    T, D = x2d.shape
    k = idx2d.shape[1]
    El = ex["gate"].shape[0]
    N = T * k
    e = idx2d.reshape(N) - e0
    held = (e >= 0) & (e < El)
    if valid is not None:
        held &= jnp.repeat(valid, k)
    key = jnp.where(held, e, El)                          # not held: last
    order = jnp.argsort(key)                              # stable
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

    y, _ = jax.lax.scan(expert, jnp.zeros((T, D), jnp.float32),
                        (starts[:-1], load, ex["gate"], ex["up"], ex["down"]))
    return y, load


def _capacity(tokens: int, k: int, num_experts: int, cf: float) -> int:
    c = int(tokens * k * cf / num_experts) + 1
    return max(8, ((c + 7) // 8) * 8)                      # 8-aligned slots


def _expert_compute_local(x2d, idx2d, w2d, gate, up, down, e0, e_local, cap):
    """Sort-based dispatch on one shard.

    x2d: (T, D); idx2d/w2d: (T, k); gate/up/down: (El, D, F)/(El, F, D).
    Returns (T, D) partial output for experts [e0, e0+El).
    """
    T, D = x2d.shape
    k = idx2d.shape[1]
    N = T * k
    flat_e = idx2d.reshape(N) - e0
    flat_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    flat_w = w2d.reshape(N)

    in_range = (flat_e >= 0) & (flat_e < e_local)
    sort_key = jnp.where(in_range, flat_e, e_local)        # invalid -> end
    order = jnp.argsort(sort_key)                          # stable
    se = sort_key[order]
    stok = flat_tok[order]
    sw = flat_w[order]
    starts = jnp.searchsorted(se, jnp.arange(e_local), side="left")
    pos = jnp.arange(N, dtype=jnp.int32) - starts[jnp.clip(se, 0, e_local - 1)]
    keep = (se < e_local) & (pos < cap)
    dest = jnp.where(keep, se * cap + pos, e_local * cap)  # trash slot at end

    slot_tok = jnp.zeros((e_local * cap + 1,), jnp.int32).at[dest].set(stok)
    slot_w = jnp.zeros((e_local * cap + 1,), x2d.dtype).at[dest].set(
        jnp.where(keep, sw, 0).astype(x2d.dtype))
    xin = x2d[slot_tok[:-1]].reshape(e_local, cap, D)       # (El,C,D)

    h = jnp.einsum("ecd,edf->ecf", xin, gate)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xin, up)
    out = jnp.einsum("ecf,efd->ecd", h, down)               # (El,C,D)

    out2 = (out.reshape(e_local * cap, D) * slot_w[:-1, None])
    y = jnp.zeros((T, D), out2.dtype).at[slot_tok[:-1]].add(out2)
    return y


def _apply_moe_a2a(cfg, mesh, x2d, idx2d, w2d, ex):
    """Sequence-sharded EP with all-to-all dispatch (§Perf optimization).

    The shard_map boundary keeps the SAME layout as the surrounding layers
    (tokens sharded over data, replicated over model) — resharding at the
    boundary provokes XLA's "involuntary full rematerialization" (measured:
    a 5x collective blow-up). Each model shard instead SLICES its row range
    locally (free on replicated data), routes those T/tp tokens, exchanges
    fixed-capacity buckets with the expert owners via ``all_to_all``,
    computes its local experts, reverses the exchange, and ``all_gather``s
    the combined rows over ``model`` (1x gather in activation dtype vs the
    baseline's 2x fp32 all-reduce; dispatch wire ~ k*cf/tp of a full pass).
    """
    E, k = cfg.num_experts, cfg.experts_per_token
    tp = mesh.shape["model"]
    e_local = E // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    T, D_model = x2d.shape
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    t_local = T // (dp * tp)
    c_send = _capacity(t_local, k, tp, cfg.capacity_factor)  # per-dest bucket
    c_comp = _capacity(tp * c_send, 1, e_local, cfg.capacity_factor)

    fsdp = ("data" in mesh.shape and mesh.shape["data"] > 1
            and cfg.name.startswith("deepseek"))
    gspec = P("model", "data", None) if fsdp else P("model", None, None)
    dspec = P("model", None, "data") if fsdp else P("model", None, None)

    # 4D row layout (dp, tp, t_local, ...) keeps the device order natural, so
    # the boundary reshard is a local split/concat the partitioner transposes
    # to an all-gather — NOT a psum (and not the "involuntary full
    # rematerialization" a flat 256-way row sharding provoked)
    rspec = P(batch_axes if batch_axes else None, "model", None, None)
    x4 = x2d.reshape(dp, tp, t_local, D_model)
    idx4 = idx2d.reshape(dp, tp, t_local, k)
    w4 = w2d.reshape(dp, tp, t_local, k)

    def shard_fn(x_blk, idx_blk, w_blk, g, u, d):
        if fsdp:
            g = jax.lax.all_gather(g, "data", axis=1, tiled=True)
            u = jax.lax.all_gather(u, "data", axis=1, tiled=True)
            d = jax.lax.all_gather(d, "data", axis=2, tiled=True)
        x_ = x_blk[0, 0]
        idx_ = idx_blk[0, 0]
        w_ = w_blk[0, 0]
        t, D = x_.shape
        N = t * k
        flat_e = idx_.reshape(N)
        flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
        flat_w = w_.reshape(N)
        dest = flat_e // e_local                          # owning shard
        order = jnp.argsort(dest)
        sdest, stok = dest[order], flat_tok[order]
        se, sw = flat_e[order], flat_w[order]
        starts = jnp.searchsorted(sdest, jnp.arange(tp), side="left")
        pos = jnp.arange(N, dtype=jnp.int32) - starts[jnp.clip(sdest, 0, tp - 1)]
        keep = pos < c_send
        slot = jnp.where(keep, sdest * c_send + pos, tp * c_send)

        # send buffers (trash slot at the end)
        x_pad = jnp.concatenate([x_, jnp.zeros((1, D), x_.dtype)], 0)
        s_tok = jnp.full((tp * c_send + 1,), t, jnp.int32).at[slot].set(stok)
        s_e = jnp.zeros((tp * c_send + 1,), jnp.int32).at[slot].set(se)
        s_w = jnp.zeros((tp * c_send + 1,), w_.dtype).at[slot].set(
            jnp.where(keep, sw, 0).astype(w_.dtype))
        s_x = x_pad[s_tok[:-1]].reshape(tp, c_send, D)
        s_e = s_e[:-1].reshape(tp, c_send)
        s_valid = (s_tok[:-1] < t).reshape(tp, c_send)

        r_x = jax.lax.all_to_all(s_x, "model", 0, 0, tiled=True)
        r_e = jax.lax.all_to_all(s_e, "model", 0, 0, tiled=True)
        r_v = jax.lax.all_to_all(s_valid, "model", 0, 0, tiled=True)

        e0 = jax.lax.axis_index("model") * e_local
        le = jnp.where(r_v, r_e - e0, e_local).reshape(tp * c_send, 1)
        ones = jnp.ones((tp * c_send, 1), x_.dtype)
        out = _expert_compute_local(r_x.reshape(tp * c_send, D),
                                    le.astype(jnp.int32), ones,
                                    g, u, d, 0, e_local, c_comp)
        out = jax.lax.all_to_all(out.reshape(tp, c_send, D), "model",
                                 0, 0, tiled=True)
        # combine: weighted scatter-add back to local tokens
        out2 = out.reshape(tp * c_send, D) * s_w[:-1, None]
        y = jnp.zeros((t + 1, D), out2.dtype).at[s_tok[:-1]].add(out2)
        return y[:-1][None, None]

    y4 = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(rspec, rspec, rspec, gspec, gspec, dspec),
        out_specs=rspec, check_vma=False,
    )(x4, idx4, w4, ex["gate"], ex["up"], ex["down"])
    # pin the result back to the surrounding batch-over-data layout so the
    # row sharding doesn't propagate into the attention layers' backward
    return shard(y4.reshape(T, D_model), "batch", None)


def apply_moe_held(p, cfg, x, valid: Optional[jax.Array] = None):
    """The held experts' part plus the shared expert, every (token, held
    expert) pair computed. x: (B,S,D); valid: (B,S) bool or None (False:
    padding, routed nowhere). Returns (out (B,S,D), rows per held expert
    (experts_held,) int32)."""
    w, idx, _ = _route(p, cfg, x)
    return _apply_held(p, cfg, x, w, idx, valid)


def _apply_held(p, cfg, x, w, idx, valid):
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    e0, _ = held_experts(cfg)
    tile = _held_tile(B * S, cfg.experts_per_token, cfg.num_experts)
    y, load = _held_expert_compute(
        x2d, idx.reshape(B * S, -1), w.reshape(B * S, -1),
        None if valid is None else valid.reshape(B * S), p["experts"], e0,
        tile)
    if "shared" in p:
        y = y + L.apply_mlp(p["shared"], x2d, "swiglu").astype(jnp.float32)
    return y.reshape(B, S, D).astype(x.dtype), load


def apply_moe(p, cfg, x) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) -> (out (B,S,D), aux_loss)."""
    B, S, D = x.shape
    w, idx, aux = _route(p, cfg, x)
    if cfg.experts_held:
        return _apply_held(p, cfg, x, w, idx, None)[0], aux
    x2d = x.reshape(B * S, D)
    idx2d = idx.reshape(B * S, -1)
    w2d = w.reshape(B * S, -1)
    E, k = cfg.num_experts, cfg.experts_per_token
    ex = p["experts"]

    mesh, _rules = axis_ctx()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    exp_rule = _rules.get("expert") if _rules else None
    ep_on = exp_rule == "model" or (isinstance(exp_rule, tuple)
                                    and "model" in exp_rule)
    strategy = current_strategy()
    if mesh is None or tp == 1 or E % tp != 0 or not ep_on:
        cap = _capacity(B * S, k, E, cfg.capacity_factor)
        y = _expert_compute_local(x2d, idx2d, w2d, ex["gate"], ex["up"],
                                  ex["down"], 0, E, cap)
    elif (strategy in ("moe_a2a", "moe_a2a_seqshard")
          and (B * S) % (tp * max(1, mesh.shape.get("data", 1)
                                  * mesh.shape.get("pod", 1))) == 0):
        y = _apply_moe_a2a(cfg, mesh, x2d, idx2d, w2d, ex)
    else:
        e_local = E // tp
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        bspec = P(batch_axes if batch_axes else None)

        # expert-weight specs mirror the param sharding rules (EP over model,
        # optional FSDP over data on the d_model dim)
        def wspec(d_axis):
            ax = [None, None, None]
            ax[0] = "model"
            if D % mesh.shape.get("data", 1) == 0 and mesh.shape.get("data", 1) > 1:
                ax[d_axis] = "data"
            return P(*ax)

        fsdp = "data" in mesh.shape and mesh.shape["data"] > 1 and cfg.name.startswith("deepseek")
        gspec = wspec(1) if fsdp else P("model", None, None)
        dspec = wspec(2) if fsdp else P("model", None, None)

        rs_ok = strategy == "moe_rs" and x2d.shape[0] % (
            tp * mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)) == 0

        def shard_fn(x2d_, idx2d_, w2d_, g, u, d):
            if fsdp:
                g = jax.lax.all_gather(g, "data", axis=1, tiled=True)
                u = jax.lax.all_gather(u, "data", axis=1, tiled=True)
                d = jax.lax.all_gather(d, "data", axis=2, tiled=True)
            e0 = jax.lax.axis_index("model") * e_local
            # capacity from the LOCAL token count (x2d_ is the local block)
            cap = _capacity(x2d_.shape[0], k, E, cfg.capacity_factor)
            y = _expert_compute_local(x2d_, idx2d_, w2d_, g, u, d,
                                      e0, e_local, cap)
            if rs_ok:
                # §Perf: reduce-scatter + bf16 all-gather — <=1/2 the wire
                # of the all-reduce (its transpose is the same pair). The
                # optimization_barrier stops XLA's collective re-association
                # pass from fusing the pair straight back into an all-reduce.
                part = jax.lax.psum_scatter(y, "model", scatter_dimension=0,
                                            tiled=True)
                part = jax.lax.optimization_barrier(
                    part.astype(jnp.bfloat16))
                return jax.lax.all_gather(part, "model",
                                          axis=0, tiled=True).astype(y.dtype)
            return jax.lax.psum(y, "model")

        y = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(bspec, bspec, bspec, gspec, gspec, dspec),
            out_specs=bspec, check_vma=False,
        )(x2d, idx2d, w2d, ex["gate"], ex["up"], ex["down"])

    if "shared" in p:
        y = y + L.apply_mlp(p["shared"], x2d, "swiglu")
    return y.reshape(B, S, D).astype(x.dtype), aux
