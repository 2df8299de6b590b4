"""Flash attention forward — Pallas TPU kernel.

Tiling: grid (BH, num_q_blocks, num_kv_blocks), kv innermost so the online
softmax statistics (m, l) and the output accumulator live in VMEM scratch
across kv steps. Block shapes default to (128, 128) — MXU-aligned (the
128x128 systolic array) and comfortably within the ~16MB/core VMEM:
q/k/v tiles at d<=256 use 3 * 128 * 256 * 4B ≈ 0.4MB plus a 128x256 fp32
accumulator. Causal masking is positional (block-level skipping is left to
the ops-level scheduler).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  num_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal block skipping: a kv block strictly above the diagonal
    # (k_min > q_max) contributes nothing — skip its two MXU matmuls
    # entirely (saves ~2x compute at long S; grid still visits the step,
    # only the body is predicated out)
    live = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, dv)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                           s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                           s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_scr[...]
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q,k,v: (BH, S, D) (v may have different last dim). Returns (BH,S,Dv).

    Lowers through Mosaic for the TPU; ``interpret=True`` runs the kernel
    body in the Pallas interpreter instead (CPU validation).
    """
    BH, S, D = q.shape
    Dv = v.shape[-1]
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    scale = D ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),     # m: running max
            pltpu.VMEM((block_q,), jnp.float32),     # l: running denom
            pltpu.VMEM((block_q, Dv), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(q, k, v)
