"""Mamba2 SSD (state-space duality) block — chunked scan + O(1)-state decode.

The chunked SSD algorithm (arXiv:2405.21060) splits the sequence into chunks
of length Q: within-chunk interactions are a (Q x Q) masked quadratic term
(MXU-friendly matmuls), and cross-chunk interactions flow through a recurrent
(H, P, N) state carried by a short ``lax.scan`` over chunks. This is the
TPU-native formulation — the CUDA kernel's warp-level selective scan is
replaced by matmuls the MXU executes at full throughput.

The projection of the input into (z | x | B | C | dt) is split into separate
matmuls (mathematically identical to the fused in_proj of the reference
implementation) so each output lands on a sharding-friendly dimension —
fused-projection slicing would cut across TP shard boundaries (DESIGN.md §2).

``repro.kernels.ssd_scan`` provides the Pallas version of the chunk scan;
this module is the pure-jnp oracle path used by dry-runs and CPU tests.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding.ctx import shard


def dims(cfg) -> Tuple[int, int, int, int]:
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_head_dim
    return d_in, nheads, cfg.ssm_ngroups, cfg.ssm_state


def init_ssm(key, cfg, dtype=jnp.float32):
    D = cfg.d_model
    d_in, H, G, N = dims(cfg)
    K = cfg.ssm_conv
    ks = jax.random.split(key, 8)
    return {
        "in_z": L.dense_init(ks[0], D, d_in, dtype),
        "in_x": L.dense_init(ks[1], D, d_in, dtype),
        "in_B": L.dense_init(ks[2], D, G * N, dtype),
        "in_C": L.dense_init(ks[3], D, G * N, dtype),
        "in_dt": L.dense_init(ks[4], D, H, dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "conv_x": (jax.random.normal(ks[5], (K, d_in), jnp.float32) * 0.1).astype(dtype),
        "conv_B": (jax.random.normal(ks[6], (K, G * N), jnp.float32) * 0.1).astype(dtype),
        "conv_C": (jax.random.normal(ks[7], (K, G * N), jnp.float32) * 0.1).astype(dtype),
        "A_log": jnp.zeros((H,), jnp.float32),          # A = -exp(A_log) = -1
        "D_skip": jnp.ones((H,), jnp.float32),
        "gate_norm": L.init_rmsnorm(d_in, dtype),
        "out": L.dense_init(jax.random.fold_in(key, 99), d_in, D, dtype),
    }


def _causal_conv(u, w):
    """Depthwise causal conv. u: (B,S,C), w: (K,C)."""
    K = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(u)
    for i in range(K):                                   # K=4: unrolled taps
        out = out + pad[:, i: i + u.shape[1], :] * w[i][None, None, :]
    return out


def ssd_chunked(xh, dt, a_log, Bm, Cm, chunk: int):
    """Chunked SSD scan (pure jnp oracle).

    xh: (B,S,H,P) inputs; dt: (B,S,H) positive step sizes;
    a_log: (H,) with A = -exp(a_log); Bm/Cm: (B,S,G,N).
    Returns y: (B,S,H,P) and final state (B,H,P,N).
    """
    Bsz, S, H, Pd = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    A = -jnp.exp(a_log.astype(jnp.float32))              # (H,) negative
    dA = dt.astype(jnp.float32) * A[None, None, :]       # (B,S,H) log-decay <0
    xbar = xh.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    # reshape to chunks
    dA_c = dA.reshape(Bsz, nc, Q, H)
    x_c = xbar.reshape(Bsz, nc, Q, H, Pd)
    B_c = jnp.repeat(Bm.astype(jnp.float32).reshape(Bsz, nc, Q, G, N), rep, axis=3)
    C_c = jnp.repeat(Cm.astype(jnp.float32).reshape(Bsz, nc, Q, G, N), rep, axis=3)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    # scan over chunks: only ONE chunk's (Q x Q) quadratic term is live at a
    # time (the all-chunks einsum materialized B*nc*H*Q*Q fp32 — 17 GB/layer
    # for zamba2's train_4k shard — and dominated temp memory; §Perf)
    def chunk_fn(state, inp):
        dA_k, x_k, B_k, C_k = inp                        # (B,Q,H), (B,Q,H,P), (B,Q,H,N)
        cum = jnp.cumsum(dA_k, axis=1)                   # (B,Q,H)
        seg = cum[:, :, None, :] - cum[:, None, :, :]    # (B,Qt,Qs,H)
        # mask before exp: above the diagonal seg > 0 and exp overflows at
        # long chunks, and where(mask, inf, 0) has a NaN gradient
        Lmat = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
        Lmat = Lmat.transpose(0, 3, 1, 2)                # (B,H,Qt,Qs)
        CB = jnp.einsum("bthn,bshn->bhts", C_k, B_k)     # (B,H,Qt,Qs)
        y = jnp.einsum("bhts,bshp->bthp", CB * Lmat, x_k)
        decay_in = jnp.exp(cum)                          # exp(l_t)
        y += jnp.einsum("bthn,bth,bhnp->bthp", C_k, decay_in, state)
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)     # (B,Q,H)
        S_chunk = jnp.einsum("bshn,bsh,bshp->bhnp", B_k, decay_to_end, x_k)
        new = state * jnp.exp(cum[:, -1, :])[:, :, None, None] + S_chunk
        return new, y

    init = jnp.zeros((Bsz, H, N, Pd), jnp.float32)
    final, ys = jax.lax.scan(
        chunk_fn, init,
        (dA_c.transpose(1, 0, 2, 3), x_c.transpose(1, 0, 2, 3, 4),
         B_c.transpose(1, 0, 2, 3, 4), C_c.transpose(1, 0, 2, 3, 4)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H, Pd)
    return y.astype(xh.dtype), final.transpose(0, 1, 3, 2)  # state (B,H,P,N)


def _ssm_seq(p, cfg, x, length=None):
    """Full-sequence chunked SSD over x: (B,S,D).

    Returns (out (B,S,D), final state (B,H,P,N) f32, the raw pre-conv
    projections (x, B, C), each (B,S,C)). With ``length`` (a traced int32),
    dt is 0 at positions >= length: their decay is 1 and their input 0, so
    the final state is the state after the first ``length`` positions."""
    B, S, D = x.shape
    d_in, H, G, N = dims(cfg)
    dt_ = x.dtype
    z = x @ p["in_z"].astype(dt_)
    xs_raw = x @ p["in_x"].astype(dt_)
    xs = _causal_conv(xs_raw, p["conv_x"].astype(dt_))
    Bm_raw = x @ p["in_B"].astype(dt_)
    Bm = _causal_conv(Bm_raw, p["conv_B"].astype(dt_))
    Cm_raw = x @ p["in_C"].astype(dt_)
    Cm = _causal_conv(Cm_raw, p["conv_C"].astype(dt_))
    xs, Bm, Cm = jax.nn.silu(xs), jax.nn.silu(Bm), jax.nn.silu(Cm)
    dt = jax.nn.softplus((x @ p["in_dt"].astype(dt_)).astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    if length is not None:
        dt = jnp.where(jnp.arange(S)[None, :, None] < length, dt, 0.0)

    xh = shard(xs.reshape(B, S, H, cfg.ssm_head_dim), "batch", None, "ssm_heads", None)
    Bm = Bm.reshape(B, S, G, N)
    Cm = Cm.reshape(B, S, G, N)
    y, state = ssd_chunked(xh, dt, p["A_log"], Bm, Cm, cfg.ssm_chunk)
    y = y + xh * p["D_skip"].astype(dt_)[None, None, :, None]
    y = y.reshape(B, S, d_in)
    y = L.apply_rmsnorm(p["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    return y @ p["out"].astype(dt_), state, (xs_raw, Bm_raw, Cm_raw)


def apply_ssm_full(p, cfg, x):
    """x: (B,S,D) -> (B,S,D). Full-sequence chunked SSD."""
    return _ssm_seq(p, cfg, x)[0]


def apply_ssm_prefill(p, cfg, x, length, cache):
    """x: (B,S,D), right-padded past ``length`` (traced int32, 1..S).

    Returns (out (B,S,D), the cache ``apply_ssm_decode`` leaves after the
    first ``length`` positions, up to rounding): the SSM state, and each
    conv window holding the raw projections at positions length-K+1 ..
    length-1, zeros before position 0."""
    out, state, raws = _ssm_seq(p, cfg, x, length)
    K = cfg.ssm_conv

    def window(u, c):
        u = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(u, length, K - 1,
                                            axis=1).astype(c.dtype)
    new = {"state": state.astype(cache["state"].dtype)}
    for name, u in zip(("conv_x", "conv_B", "conv_C"), raws):
        new[name] = window(u, cache[name])
    return out, new


def init_ssm_cache(cfg, batch: int, dtype=jnp.float32):
    d_in, H, G, N = dims(cfg)
    K = cfg.ssm_conv
    return {
        "state": jnp.zeros((batch, H, cfg.ssm_head_dim, N), jnp.float32),
        "conv_x": jnp.zeros((batch, K - 1, d_in), dtype),
        "conv_B": jnp.zeros((batch, K - 1, G * N), dtype),
        "conv_C": jnp.zeros((batch, K - 1, G * N), dtype),
    }


def _conv_step(u1, conv_state, w):
    """u1: (B,1,C); conv_state: (B,K-1,C); w: (K,C)."""
    window = jnp.concatenate([conv_state, u1], axis=1)    # (B,K,C)
    out = jnp.einsum("bkc,kc->bc", window, w)[:, None, :]
    return out, window[:, 1:, :]


def apply_ssm_decode(p, cfg, x, cache):
    """x: (B,1,D); O(1)-state recurrent decode step."""
    B = x.shape[0]
    d_in, H, G, N = dims(cfg)
    Pd = cfg.ssm_head_dim
    dt_ = x.dtype
    z = x @ p["in_z"].astype(dt_)
    xs_raw = x @ p["in_x"].astype(dt_)
    Bm_raw = x @ p["in_B"].astype(dt_)
    Cm_raw = x @ p["in_C"].astype(dt_)
    xs, cs_x = _conv_step(xs_raw, cache["conv_x"], p["conv_x"].astype(dt_))
    Bm, cs_B = _conv_step(Bm_raw, cache["conv_B"], p["conv_B"].astype(dt_))
    Cm, cs_C = _conv_step(Cm_raw, cache["conv_C"], p["conv_C"].astype(dt_))
    xs, Bm, Cm = jax.nn.silu(xs), jax.nn.silu(Bm), jax.nn.silu(Cm)
    dt = jax.nn.softplus((x @ p["in_dt"].astype(dt_)).astype(jnp.float32)
                         + p["dt_bias"][None, None, :])[:, 0]        # (B,H)

    xh = xs.reshape(B, H, Pd).astype(jnp.float32)
    Bv = jnp.repeat(Bm.reshape(B, G, N), H // G, axis=1).astype(jnp.float32)
    Cv = jnp.repeat(Cm.reshape(B, G, N), H // G, axis=1).astype(jnp.float32)

    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt * A[None, :])                      # (B,H)
    state = cache["state"] * decay[:, :, None, None]
    state = state + jnp.einsum("bhp,bhn,bh->bhpn", xh, Bv, dt)
    y = jnp.einsum("bhpn,bhn->bhp", state, Cv)
    y = y + xh * p["D_skip"][None, :, None]
    y = y.reshape(B, 1, d_in).astype(dt_)
    y = L.apply_rmsnorm(p["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out"].astype(dt_)
    return out, {"state": state, "conv_x": cs_x, "conv_B": cs_B, "conv_C": cs_C}
