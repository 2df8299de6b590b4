"""Fused RMSNorm — Pallas TPU kernel.

Row-tiled: grid over row blocks, each step normalizes (block_rows, D) in
one VMEM-resident pass (read once, write once — the fusion avoids the
separate mean/var and scale passes XLA sometimes emits around mixed-dtype
residual streams).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "eps", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    """x: (R, D); scale: (D,). Returns (R, D)."""
    R, D = x.shape
    block_rows = min(block_rows, R)
    assert R % block_rows == 0, (R, block_rows)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        interpret=interpret,
    )(x, scale)
