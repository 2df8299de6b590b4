"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid (BH, num_chunks), chunks innermost and sequential; the recurrent
(N, P) state lives in VMEM scratch across chunk steps (same persist-scratch
pattern as flash attention). Per chunk, the within-chunk quadratic term is
two MXU matmuls ((Q,N)@(N,Q) and (Q,Q)@(Q,P)) — the TPU-native SSD
formulation (DESIGN.md §3) — and the cross-chunk term is one (Q,N)@(N,P).

Block sizes: chunk Q=128/256 rows, state N<=256, head dim P<=128 keep the
working set (Q*N + Q*P + N*P + Q*Q fp32) well under 2MB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, da_ref, b_ref, c_ref, y_ref, state_scr, *,
                num_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    da = da_ref[0].astype(jnp.float32)        # (1, Q)
    b = b_ref[0].astype(jnp.float32)          # (Q, N)
    c = c_ref[0].astype(jnp.float32)          # (Q, N)

    # cumulative log-decay as a column (Q, 1) and a row (1, Q). Mosaic has
    # no cumsum, so both are matmuls with the causal mask: cum_t = sum_{s<=t}
    Q = x.shape[0]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    trif = tri.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot_general(trif, da, (((1,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    cum_row = jax.lax.dot_general(da, trif, (((1,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)
    total = jnp.sum(da, axis=1, keepdims=True)  # (1, 1) = cum[-1]

    # within-chunk decayed attention-like term
    seg = cum_col - cum_row                   # l_t - l_s
    L = jnp.where(tri, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    y = jax.lax.dot_general(cb * L, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)

    # contribution of previous chunks through the carried state
    state = state_scr[...]                    # (N, P)
    decay_in = jnp.exp(cum_col)               # (Q, 1)
    y += jax.lax.dot_general(c * decay_in, state,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: S <- S * exp(cum[-1]) + sum_s exp(cum[-1]-cum_s) B_s x_s
    decay_out = jnp.exp(total - cum_col)      # (Q, 1)
    new_state = state * jnp.exp(total) + jax.lax.dot_general(
        b * decay_out, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # (N, P)
    state_scr[...] = new_state
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dA, Bm, Cm, *, chunk: int = 128, interpret: bool = False):
    """x: (BH, S, P); dA: (BH, S) log-decays; Bm/Cm: (BH, S, N).

    Returns y: (BH, S, P). Chunk must divide S.
    """
    BH, S, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, num_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            # dA goes in as (BH, 1, S): Mosaic needs a block's second-to-last
            # dim to be a multiple of 8 or the whole array dim, and a
            # (1, chunk) block over (BH, S) is neither
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dA[:, None, :], Bm, Cm)
