"""Gradient compression: int8 quantized all-reduce with error feedback.

Wire format: per-leaf max-abs scale (fp32 scalar, psum-MAX'd) + int8 payload.
The reduction is chunked ring-style under ``shard_map``:

    all_to_all(int8 chunks) -> local int32 sum -> requantize -> all_gather

moving ~2x int8 bytes per device instead of 2x fp32 — a ~4x wire reduction
vs fp32 all-reduce (~2x vs bf16), at <1e-2 relative error with error
feedback absorbing the quantization residual across steps.

Integrated into ``make_dp_train_step`` for pure-DP meshes (the ``model``
axis must be trivial — with tensor parallelism the gradient psum is fused
into the backward pass by SPMD and cannot be intercepted at this layer; the
TP-side reduction-precision lever lives in the model code instead, see
EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize(g: jax.Array, scale: jax.Array) -> jax.Array:
    q = jnp.clip(jnp.round(g / scale * 127.0), -127, 127)
    return q.astype(jnp.int8)


def _dequantize(q: jax.Array, scale: jax.Array, n: int) -> jax.Array:
    return q.astype(jnp.float32) * scale / 127.0 / n


def compressed_psum_mean(g: jax.Array, axis: str) -> jax.Array:
    """int8 ring all-reduce-mean over ``axis`` (call inside shard_map)."""
    n = jax.lax.axis_size(axis)
    flat = g.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    scale = jax.lax.pmax(jnp.max(jnp.abs(flat)) + 1e-12, axis)

    chunks = flat.reshape(n, -1)
    q = _quantize(chunks, scale)                       # (n, c) int8
    # reduce-scatter: every device receives peers' copy of ITS chunk
    mine = jax.lax.all_to_all(q[:, None, :], axis, split_axis=0,
                              concat_axis=1, tiled=False)
    # mine: (1, n, c) int8 -> int32 sum
    local_sum = jnp.sum(mine.astype(jnp.int32), axis=(0, 1))   # (c,)
    # requantize the partial sums and all-gather
    q_sum = jnp.clip(local_sum, -32767, 32767).astype(jnp.int16)
    full = jax.lax.all_gather(q_sum, axis, axis=0, tiled=False)  # (n, c)
    out = _dequantize(full.reshape(-1), scale, n)
    if pad:
        out = out[:-pad]
    return out.reshape(g.shape)


def compressed_tree_psum_mean(grads, axis: str, err=None):
    """Per-leaf compressed mean-reduce with error feedback state."""
    if err is None:
        err = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)

    def one(g, e):
        g = g.astype(jnp.float32) + e
        red = compressed_psum_mean(g, axis)
        # residual between what we contributed and what quantization kept
        kept = compressed_psum_mean(jnp.zeros_like(g), axis) * 0 + red
        new_e = g - red                      # local error feedback
        return red, new_e
    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]))


def make_compressed_grad_fn(loss_fn, mesh, data_axes=("data",)):
    """Returns grads_fn(params, err, batch) -> (loss, grads, new_err) with the
    data-parallel reduction done via the int8 path under shard_map.

    Requires the model to be pure-DP (no TP constraints inside) — used by
    the compression benchmark/tests and pure-DP training configs."""
    axis = data_axes[0]

    def local_grads(params, err, batch):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        red, new_err = compressed_tree_psum_mean(g, axis, err)
        loss = jax.lax.pmean(loss, axis)
        return loss, red, new_err

    pspec = jax.tree.map(lambda _: P(), jax.tree.structure(None))  # unused

    def wrapped(params, err, batch):
        rep = lambda t: jax.tree.map(lambda _: P(), t)
        bspec = jax.tree.map(lambda _: P(axis), batch)
        return jax.shard_map(local_grads, mesh=mesh,
                             in_specs=(rep(params), rep(err), bspec),
                             out_specs=(P(), rep(params), rep(err)),
                             check_vma=False)(params, err, batch)
    return wrapped
