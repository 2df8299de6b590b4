"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

Stages hold disjoint layer blocks (stacked params, leading ``stage`` dim);
microbatches stream through via ``ppermute`` in the classic (M + S - 1)-tick
schedule. Backward works through autodiff (ppermute transposes to the
reverse permute), giving GPipe semantics (full activation stash; combine
with remat for the memory-optimal variant).

This is the PP building block required "as appropriate" at scale —
the assigned production meshes use DP x TP (+EP/SP); PP composes on a
(stage, data, model) mesh for cross-pod layer sharding where ICI is scarce.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, mesh: Mesh, *, stage_axis: str = "stage",
                   num_microbatches: int):
    """Returns f(stage_params, x) -> y running the pipeline.

    stage_params: pytree with leading [num_stages] dim on every leaf.
    x: (num_microbatches, mb, ...) input microbatches.
    stage_fn(params_slice, mb_input) -> mb_output (same shape as input).
    """
    S = mesh.shape[stage_axis]
    M = num_microbatches

    def local(params, x):
        # params: leaves sliced to this stage: leading dim 1 -> squeeze
        params = jax.tree.map(lambda p: p[0], params)
        sid = jax.lax.axis_index(stage_axis)
        x = x[0]                                   # (M, mb, ...) local copy
        mb_shape = x.shape[1:]
        buf = jnp.zeros(mb_shape, x.dtype)         # current carried activation
        outs = jnp.zeros_like(x)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range); others use recv'd buf
            mb_idx = jnp.clip(t, 0, M - 1)
            inp = jnp.where(sid == 0,
                            x[mb_idx],
                            buf)
            out = stage_fn(params, inp)
            # last stage records its finished microbatch (t - (S-1))
            done_idx = t - (S - 1)
            record = jnp.logical_and(sid == S - 1, done_idx >= 0)
            outs = jax.lax.cond(
                record,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(done_idx, 0), 0),
                lambda o: o, outs)
            # shift activations to the next stage
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf = jax.lax.ppermute(out, stage_axis, perm)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, M + S - 1, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast to all stages
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), stage_axis)
        return outs[None]

    pspec = P(stage_axis)

    def run(stage_params, x):
        in_specs = (jax.tree.map(lambda _: pspec, stage_params),
                    P(stage_axis))
        y = jax.shard_map(local, mesh=mesh,
                          in_specs=in_specs, out_specs=P(stage_axis),
                          check_vma=False)(
            stage_params,
            jnp.broadcast_to(x[None], (S,) + x.shape))
        return y[0]
    return run
