"""The plain references against the program's model at a tiny size on the
CPU: the same weights from the same seed, and the same logits.

Tolerances, each with its reason:

* The references build their weights with their own code; drawn from the
  same key in one jitted call they must equal the program's bit for bit.
* Program and reference both in float32 at ``highest`` precision sum the
  same products in different orders (blocked attention and the chunked SSD
  scan against a plain softmax and plain blocks): F32_RTOL = 1e-4 of the
  largest logit, ~1000 float32 roundings.
* The program as configured (bf16 weights and activations) keeps 8
  significant bits: at 2 layers its logits lie within BF16_RTOL = 0.05 of
  the largest logit of the float32 reference, and the float8 control lies
  at least 5 times further away than the bf16 program does.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import transformer as T
from bench.reference import common as C, dense, ssm

F32_RTOL = 1e-4
BF16_RTOL = 0.05
FAMILIES = [("mamba2-370m", ssm), ("stablelm-1.6b", dense)]


def tiny(arch, **kw):
    cfg = reduced(get_arch(arch).model).replace(**kw)
    return cfg, dataclasses.asdict(cfg)


def tokens(cfg, b=2, s=48, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0,
                              cfg.vocab_size)


def exact(ref, params, m, toks, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.logits(params, m, toks, C.Numerics(), **kw)


@pytest.mark.parametrize("arch,ref", FAMILIES)
def test_init_equals_program_init(arch, ref):
    cfg, m = tiny(arch)
    key = jax.random.PRNGKey(2 ** 31 + 3)
    prog = jax.jit(lambda k: T.init_lm(k, cfg))(key)
    mine = jax.jit(lambda k: ref.init(m, k))(key)
    fp, _ = jax.tree_util.tree_flatten_with_path(prog)
    fr, _ = jax.tree_util.tree_flatten_with_path(mine)
    assert [jax.tree_util.keystr(k) for k, _ in fp] == \
        [jax.tree_util.keystr(k) for k, _ in fr]
    for (k, a), (_, b) in zip(fp, fr):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b)), k


@pytest.mark.parametrize("arch,ref", FAMILIES)
def test_float32_forward_matches_program(arch, ref):
    cfg, m = tiny(arch, param_dtype="float32", compute_dtype="float32")
    params = jax.jit(lambda k: ref.init(m, k))(jax.random.PRNGKey(5))
    toks = tokens(cfg)
    with jax.default_matmul_precision("highest"):
        prog = T.apply_lm(params, cfg, toks)[0]
    got = exact(ref, params, m, toks)
    scale = float(jnp.max(jnp.abs(got)))
    assert float(jnp.max(jnp.abs(prog - got))) <= F32_RTOL * scale


@pytest.mark.parametrize("arch,ref", FAMILIES)
def test_decode_path_matches_reference(arch, ref):
    """Prefill through the decode path, then decoding, against the
    reference's full forward at every position."""
    cfg, m = tiny(arch, param_dtype="float32", compute_dtype="float32")
    params = jax.jit(lambda k: ref.init(m, k))(jax.random.PRNGKey(6))
    toks = tokens(cfg, s=20)
    caches = T.init_caches(cfg, 2, 20, jnp.float32)
    step = jax.jit(lambda p, t, c, i: T.apply_lm_decode(p, cfg, t, c, i))
    with jax.default_matmul_precision("highest"):
        outs = []
        for i in range(20):
            lg, caches = step(params, toks[:, i:i + 1], caches, jnp.int32(i))
            outs.append(lg[:, 0])
    got = exact(ref, params, m, toks)
    scale = float(jnp.max(jnp.abs(got)))
    assert float(jnp.max(jnp.abs(jnp.stack(outs, 1) - got))) \
        <= F32_RTOL * scale


@pytest.mark.parametrize("arch,ref", FAMILIES)
def test_bf16_program_within_tolerance_and_control_outside(arch, ref):
    cfg, m = tiny(arch)
    params = jax.jit(lambda k: ref.init(m, k))(jax.random.PRNGKey(8))
    toks = tokens(cfg)
    got = exact(ref, params, m, toks)
    scale = float(jnp.max(jnp.abs(got)))
    prog = float(jnp.max(jnp.abs(T.apply_lm(params, cfg, toks)[0] - got)))
    with jax.default_matmul_precision("highest"):
        ctrl = ref.logits(params, m, toks, C.Numerics(control=True))
    ctrl = float(jnp.max(jnp.abs(ctrl - got)))
    assert prog <= BF16_RTOL * scale
    assert ctrl >= 5 * prog


def test_blocked_ssd_equals_sequential_recurrence():
    cfg, m = tiny("mamba2-370m", param_dtype="float32",
                  compute_dtype="float32")
    params = jax.jit(lambda k: ssm.init(m, k))(jax.random.PRNGKey(9))
    toks = tokens(cfg, s=37)              # not a multiple of the block
    seq = exact(ssm, params, m, toks, sequential=True)
    blk = exact(ssm, params, m, toks)
    assert float(jnp.max(jnp.abs(seq - blk))) <= \
        F32_RTOL * float(jnp.max(jnp.abs(seq)))


def test_blocked_train_step_equals_whole_batch():
    """The reference's gradient taken a row at a time equals the whole
    batch's (the loss is a mean over rows)."""
    cfg, m = tiny("mamba2-370m", param_dtype="float32",
                  compute_dtype="float32")
    tr = dataclasses.asdict(get_arch("mamba2-370m").train)
    params = jax.jit(lambda k: ssm.init(m, k))(jax.random.PRNGKey(4))
    toks = np.asarray(tokens(cfg, b=4, s=33))
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    loss = lambda p, b: ssm.loss(p, m, b, C.Numerics())
    with jax.default_matmul_precision("highest"):
        whole = C.make_train_step(loss, tr)(params, C.adamw_init(params),
                                            batch)
        rows = C.make_train_step(loss, tr, rows=1)(
            params, C.adamw_init(params), batch)
    assert abs(float(whole[2]) - float(rows[2])) <= 1e-5 * float(whole[2])
    for a, b in zip(jax.tree.leaves(whole[3]), jax.tree.leaves(rows[3])):
        assert abs(float(a) - float(b)) <= 1e-4 * max(float(a), 1e-6)
