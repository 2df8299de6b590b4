"""Plain float32 pieces shared by the references.

Nothing here imports the program. The initialisers draw the same numbers
from the same seed as the architecture's published initialisation does in
the program (normal draws scaled by 1/sqrt(fan_in), embeddings by 0.02, in
the parameter dtype the configuration states), so a reference built from a
seed holds the weights the program was built with, without taking them from
the program. ``tests/test_reference.py`` checks that they agree bit for bit.

The forward and backward passes run in float32 with every matmul at
``highest`` precision. Parameters stay stored in the dtype the configuration
states (bf16 matrices), since that is the model as stated: an AdamW update
smaller than half a bf16 step leaves such a weight where it is, in the
program and in the reference alike.

``fake_quant`` turns the reference into the control: every matmul operand is
rounded to float8 (e4m3, scaled per tensor), the next precision below bf16,
and in training every gradient flowing back through one to float8 e5m2.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}
FP8_MAX = 448.0           # largest finite float8_e4m3fn
FP8_E5M2_MAX = 57344.0    # largest finite float8_e5m2


def dense_init(key, n_in: int, n_out: int, dtype):
    return (jax.random.normal(key, (n_in, n_out), jnp.float32)
            * (1.0 / np.sqrt(n_in))).astype(dtype)


def ones(n: int, dtype):
    return jnp.ones((n,), dtype)


def stack_layers(init_one: Callable, key, n: int):
    """Per-layer parameters drawn from ``split(key, n)``, stacked on a
    leading layer axis."""
    return jax.lax.map(init_one, jax.random.split(key, n))


def padded_vocab(vocab: int, multiple: int) -> int:
    return -(-vocab // multiple) * multiple


# ----------------------------------------------------------------------
# numerics
# ----------------------------------------------------------------------

def _round8(x, dtype, top: float):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fake_quant(x):
    """Round ``x`` to float8 e4m3 with a per-tensor scale, back in f32;
    its gradient is rounded to float8 e5m2 with a scale of its own, as
    float8 training does."""
    return _round8(x, jnp.float8_e4m3fn, FP8_MAX)


def _fq_fwd(x):
    return fake_quant(x), None


def _fq_bwd(_, g):
    return (_round8(g, jnp.float8_e5m2, FP8_E5M2_MAX),)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


class Numerics:
    """How the reference multiplies: exact float32, or the control's
    float8 operands."""

    def __init__(self, control: bool = False):
        self.control = control

    def mm(self, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.control:
            a, b = fake_quant(a), fake_quant(b)
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def einsum(self, spec: str, *ops):
        ops = [o.astype(jnp.float32) for o in ops]
        if self.control:
            ops = [fake_quant(o) for o in ops]
        return jnp.einsum(spec, *ops, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# ----------------------------------------------------------------------
# loss in row blocks, so the full (B, S, V) logits never exist at once
# ----------------------------------------------------------------------

def blocked_cross_entropy(head: Callable, h, targets, rows: int = 1):
    """Mean next-token cross-entropy of ``head(h)`` over all positions,
    computed ``rows`` batch rows at a time (each block recomputed in the
    backward pass)."""
    B = h.shape[0]
    rows = min(rows, B)
    hb = h.reshape((B // rows, rows) + h.shape[1:])
    tb = targets.reshape((B // rows, rows) + targets.shape[1:])

    @jax.checkpoint
    def block(carry, xs):
        hh, tt = xs
        logits = head(hh)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tt[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (hb, tb))
    return total / targets.size


# ----------------------------------------------------------------------
# AdamW with global-norm clipping, as the configuration's TrainConfig states
# ----------------------------------------------------------------------

def adamw_init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"mu": jax.tree.map(z, params), "nu": jax.tree.map(z, params),
            "count": jnp.zeros((), jnp.int32)}


def clip_global(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_step(params, opt, grads, t: Dict[str, Any]):
    count = opt["count"] + 1
    c = count.astype(jnp.float32)
    bc1, bc2 = 1.0 - t["beta1"] ** c, 1.0 - t["beta2"] ** c

    def one(p, g, m, v):
        m = t["beta1"] * m + (1 - t["beta1"]) * g
        v = t["beta2"] * v + (1 - t["beta2"]) * g * g
        p32 = p.astype(jnp.float32)
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + 1e-8) \
            + t["weight_decay"] * p32
        return (p32 - t["learning_rate"] * upd).astype(p.dtype), m, v

    out = jax.tree.map(one, params, grads, opt["mu"], opt["nu"])
    pick = lambda i: jax.tree.map(lambda _, o: o[i], params, out)
    return pick(0), {"mu": pick(1), "nu": pick(2), "count": count}


def make_train_step(loss_fn: Callable, tcfg: Dict[str, Any], rows: int = 0):
    """(params, opt, batch) -> (params, opt, loss, norm of each leaf of the
    clipped gradient). The loss and its gradient are taken in float32 from
    the stored parameters; with ``rows``, ``rows`` batch rows at a time
    (the loss is a mean over rows, so the mean of the blocks' gradients is
    the batch's gradient)."""
    grad_fn = jax.value_and_grad(loss_fn)

    def loss_and_grads(p32, batch):
        B = batch["tokens"].shape[0]
        if not rows or rows >= B:
            return grad_fn(p32, batch)
        blocks = jax.tree.map(
            lambda x: x.reshape((B // rows, rows) + x.shape[1:]), batch)

        def body(acc, blk):
            loss, g = grad_fn(p32, blk)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None
        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p32))
        (loss, grads), _ = jax.lax.scan(body, zero, blocks)
        n = B // rows
        return loss / n, jax.tree.map(lambda g: g / n, grads)

    def step(params, opt, batch):
        loss, grads = loss_and_grads(f32(params), batch)
        grads = clip_global(grads, tcfg["grad_clip"])
        params, opt = adamw_step(params, opt, grads, tcfg)
        return params, opt, loss, jax.tree.map(
            lambda g: jnp.sqrt(jnp.sum(g * g)), grads)
    return step


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, keyed by its path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(
        jnp.square(v.astype(jnp.float32))))) for k, v in flat}


def leaf_change_norms(new, old) -> Dict[str, float]:
    flat_n, _ = jax.tree_util.tree_flatten_with_path(new)
    flat_o = jax.tree.leaves(old)
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(jnp.square(
        n.astype(jnp.float32) - o.astype(jnp.float32)))))
        for (k, n), o in zip(flat_n, flat_o)}
