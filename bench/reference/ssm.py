"""Plain float32 reference of a Mamba-2 language model (arXiv:2405.21060).

Each layer is ``h + Mixer(RMSNorm(h))``; the mixer projects the input to a
gate z, inputs x, B, C and a step size dt, runs x, B and C through a causal
depthwise convolution of width ``ssm_conv`` and SiLU, and applies the SSD
recurrence per head (head size ``ssm_head_dim``, state ``ssm_state``, one
B and C per group):

    state_t = exp(dt_t * A) * state_{t-1} + dt_t * x_t (outer) B_t
    y_t     = state_t . C_t + D * x_t

then ``out(RMSNorm(y * silu(z)))``. The output head is the embedding
transposed (tied).

``ssd_sequential`` runs that recurrence one position at a time: it is the
definition, and the tests hold the blocked form to it at small sizes.
``ssd_blocked`` computes the same sums a block of positions at a time
(within a block as a masked matrix of decays, across blocks through the
carried state), which is what makes the reference affordable at 2,048
positions and 48 layers on the chip.

Departures of the configuration as run from the published Mamba-2, which
the reference follows because they are the model as configured: the
convolution has no bias; dt_bias starts at 0 and A_log at 0 (A = -1); the
vocabulary is padded to a multiple of ``pad_vocab_multiple`` and the padded
rows are ordinary (random) rows of the tied embedding.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference import common as C

# positions per block of ``ssd_blocked``: the reference's own choice (any
# block gives the same sums); 64 keeps the (block x block) decay matrices of
# a 8 x 2048 training batch small enough for the backward pass to fit
BLOCK = 64


def dims(m: Dict[str, Any]):
    d_in = m["ssm_expand"] * m["d_model"]
    return d_in, d_in // m["ssm_head_dim"], m["ssm_ngroups"], m["ssm_state"]


# ----------------------------------------------------------------------
# parameters from the seed
# ----------------------------------------------------------------------

def init(m: Dict[str, Any], key):
    dtype = C.DTYPES[m["param_dtype"]]
    D = m["d_model"]
    d_in, H, G, N = dims(m)
    K = m["ssm_conv"]
    V = C.padded_vocab(m["vocab_size"], m["pad_vocab_multiple"])
    ks = jax.random.split(key, 8)

    def layer(k):
        kk = jax.random.split(k, 8)
        conv = lambda kc, c: (jax.random.normal(kc, (K, c), jnp.float32)
                              * 0.1).astype(dtype)
        return {"ln": {"scale": C.ones(D, dtype)}, "ssm": {
            "in_z": C.dense_init(kk[0], D, d_in, dtype),
            "in_x": C.dense_init(kk[1], D, d_in, dtype),
            "in_B": C.dense_init(kk[2], D, G * N, dtype),
            "in_C": C.dense_init(kk[3], D, G * N, dtype),
            "in_dt": C.dense_init(kk[4], D, H, dtype),
            "dt_bias": jnp.zeros((H,), jnp.float32),
            "conv_x": conv(kk[5], d_in),
            "conv_B": conv(kk[6], G * N),
            "conv_C": conv(kk[7], G * N),
            "A_log": jnp.zeros((H,), jnp.float32),
            "D_skip": jnp.ones((H,), jnp.float32),
            "gate_norm": {"scale": C.ones(d_in, dtype)},
            "out": C.dense_init(jax.random.fold_in(k, 99), d_in, D, dtype),
        }}

    return {"embed": {"table": (jax.random.normal(ks[0], (V, D), jnp.float32)
                                * 0.02).astype(dtype)},
            "final_norm": {"scale": C.ones(D, dtype)},
            "layers": C.stack_layers(layer, ks[2], m["num_layers"])}


# ----------------------------------------------------------------------
# the SSD recurrence
# ----------------------------------------------------------------------

def _per_head(Bm, H):
    """(b, s, G, N) -> (b, s, H, N): head h reads group h // (H / G)."""
    return jnp.repeat(Bm, H // Bm.shape[2], axis=2)


def ssd_sequential(x, dt, A, Bm, Cm):
    """x: (b,s,H,P); dt: (b,s,H); A: (H,); Bm, Cm: (b,s,G,N)."""
    b, s, H, P = x.shape
    Bh, Ch = _per_head(Bm, H), _per_head(Cm, H)

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, C_t,
                                 precision=jax.lax.Precision.HIGHEST)

    state0 = jnp.zeros((b, H, P, Bm.shape[-1]), jnp.float32)
    seq = lambda a: jnp.moveaxis(a, 1, 0)
    _, ys = jax.lax.scan(step, state0, (seq(x), seq(dt), seq(Bh), seq(Ch)))
    return jnp.moveaxis(ys, 0, 1)


def ssd_blocked(x, dt, A, Bm, Cm, block: int, num: C.Numerics):
    """The same sums as ``ssd_sequential``, ``block`` positions at a time."""
    b, s, H, P = x.shape
    Q = min(block, s)
    nb = -(-s // Q)
    # positions past the end (dt = 0, x = B = C = 0) change no earlier sum
    pad = lambda a: jnp.pad(a, ((0, 0), (0, nb * Q - s))
                            + ((0, 0),) * (a.ndim - 2))
    x, dt, Bh, Ch = (pad(a) for a in (x, dt, _per_head(Bm, H),
                                      _per_head(Cm, H)))
    blk = lambda a: jnp.moveaxis(a.reshape((b, nb, Q) + a.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one_block(state, inp):
        x_k, dt_k, B_k, C_k = inp                     # (b,Q,H,...)
        la = jnp.cumsum(dt_k * A, axis=1)             # log decay to t
        diff = la[:, :, None, :] - la[:, None, :, :]  # (b,t,s,H)
        decay = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
        u = dt_k[..., None] * x_k                     # (b,Q,H,P)
        scores = num.einsum("bthn,bshn->btsh", C_k, B_k) * decay
        y = num.einsum("btsh,bshp->bthp", scores, u)
        y = y + jnp.exp(la)[..., None] * num.einsum("bthn,bhpn->bthp",
                                                    C_k, state)
        to_end = jnp.exp(la[:, -1:, :] - la)          # (b,Q,H)
        state = (jnp.exp(la[:, -1, :])[..., None, None] * state
                 + num.einsum("bshp,bshn->bhpn", to_end[..., None] * u, B_k))
        return state, y

    state0 = jnp.zeros((b, H, P, Bm.shape[-1]), jnp.float32)
    _, ys = jax.lax.scan(one_block, state0,
                         (blk(x), blk(dt), blk(Bh), blk(Ch)))
    return jnp.moveaxis(ys, 0, 1).reshape(b, nb * Q, H, P)[:, :s]


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------

def _conv(u, w):
    """Causal depthwise convolution; u: (b,s,c), w: (K,c)."""
    K, s = w.shape[0], u.shape[1]
    pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(pad[:, i:i + s] * w[i] for i in range(K))


def mixer(p, m, x, num: C.Numerics, sequential: bool = False):
    b, s, _ = x.shape
    d_in, H, G, N = dims(m)
    P = m["ssm_head_dim"]
    z = num.mm(x, p["in_z"])
    xs = jax.nn.silu(_conv(num.mm(x, p["in_x"]), p["conv_x"]))
    Bm = jax.nn.silu(_conv(num.mm(x, p["in_B"]), p["conv_B"]))
    Cm = jax.nn.silu(_conv(num.mm(x, p["in_C"]), p["conv_C"]))
    dt = jax.nn.softplus(num.mm(x, p["in_dt"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(b, s, H, P)
    Bm, Cm = Bm.reshape(b, s, G, N), Cm.reshape(b, s, G, N)
    if sequential:
        y = ssd_sequential(xh, dt, A, Bm, Cm)
    else:
        y = ssd_blocked(xh, dt, A, Bm, Cm, BLOCK, num)
    y = (y + xh * p["D_skip"][:, None]).reshape(b, s, d_in)
    y = C.rmsnorm(y * jax.nn.silu(z), p["gate_norm"]["scale"], m["norm_eps"])
    return num.mm(y, p["out"])


def hidden(params, m, tokens, num: C.Numerics, sequential: bool = False):
    """Final-normed hidden states (b, s, D) in float32."""
    h = jnp.take(params["embed"]["table"], tokens, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def layer(h, lp):
        x = C.rmsnorm(h, lp["ln"]["scale"], m["norm_eps"])
        return h + mixer(lp["ssm"], m, x, num, sequential), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    return C.rmsnorm(h, params["final_norm"]["scale"], m["norm_eps"])


def head(params, num: C.Numerics):
    table = params["embed"]["table"]
    return lambda h: num.mm(h, table.T)


def logits(params, m, tokens, num: C.Numerics, sequential: bool = False):
    return head(params, num)(hidden(params, m, tokens, num, sequential))


def loss(params, m, batch, num: C.Numerics):
    h = hidden(params, m, batch["tokens"], num)
    return C.blocked_cross_entropy(head(params, num), h,
                                   batch["targets"])
