"""Per-kernel shape/dtype sweeps, assert_allclose vs the ref.py oracles
(interpret mode on CPU; the kernels TARGET TPU via BlockSpecs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("S,D,blocks", [(128, 64, (128, 128)),
                                        (256, 64, (128, 128)),
                                        (256, 128, (128, 64)),
                                        (512, 32, (128, 128))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, D, blocks, dtype):
    key = jax.random.PRNGKey(S + D)
    q = _rand(key, (2, S, D), dtype)
    k = _rand(jax.random.fold_in(key, 1), (2, S, D), dtype)
    v = _rand(jax.random.fold_in(key, 2), (2, S, D), dtype)
    o = flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1],
                        interpret=True)
    o_ref = ref.reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_non_causal():
    key = jax.random.PRNGKey(9)
    q = _rand(key, (1, 128, 32), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (1, 128, 32), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (1, 128, 32), jnp.float32)
    o = flash_attention(q, k, v, causal=False, interpret=True)
    o_ref = ref.reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


def test_flash_attention_mixed_v_dim():
    """MLA-style: qk head dim != v head dim."""
    key = jax.random.PRNGKey(10)
    q = _rand(key, (2, 128, 48), jnp.float32)
    k = _rand(jax.random.fold_in(key, 1), (2, 128, 48), jnp.float32)
    v = _rand(jax.random.fold_in(key, 2), (2, 128, 32), jnp.float32)
    o = flash_attention(q, k, v, interpret=True)
    o_ref = ref.reference_attention(q, k, v)
    assert o.shape == (2, 128, 32)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


@pytest.mark.parametrize("S,P,N,chunk", [(128, 16, 32, 32), (256, 32, 16, 64),
                                         (128, 64, 64, 128), (64, 8, 8, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(S, P, N, chunk, dtype):
    key = jax.random.PRNGKey(S * P + N)
    x = _rand(key, (2, S, P), dtype)
    dA = (-jax.nn.softplus(jax.random.normal(
        jax.random.fold_in(key, 1), (2, S)))).astype(jnp.float32)
    Bm = (_rand(jax.random.fold_in(key, 2), (2, S, N), dtype) * 0.5).astype(dtype)
    Cm = (_rand(jax.random.fold_in(key, 3), (2, S, N), dtype) * 0.5).astype(dtype)
    y = ssd_scan(x, dA, Bm, Cm, chunk=chunk, interpret=True)
    y_ref, _ = ref.reference_ssd(x, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=TOL[dtype] * 10, rtol=TOL[dtype] * 10)


@pytest.mark.parametrize("R,D,br", [(256, 64, 128), (512, 128, 256),
                                    (128, 96, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(R, D, br, dtype):
    key = jax.random.PRNGKey(R + D)
    x = _rand(key, (R, D), dtype)
    s = _rand(jax.random.fold_in(key, 1), (D,), jnp.float32)
    y = rmsnorm(x, s, block_rows=br, interpret=True)
    y_ref = ref.reference_rmsnorm(x, s)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_model_blockwise_attention_vs_oracle():
    """The model's jnp blockwise (flash-semantics) attention vs oracle."""
    from repro.models.attention import blockwise_attention
    key = jax.random.PRNGKey(11)
    B, H, S, hd = 2, 3, 200, 16        # S deliberately NOT block-divisible
    q = jax.random.normal(key, (B, H, S, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, hd))
    pos = jnp.arange(S, dtype=jnp.int32)
    out = blockwise_attention(q, k, v, pos, pos, block=64)
    o_ref = ref.reference_attention(q.reshape(B * H, S, hd),
                                    k.reshape(B * H, S, hd),
                                    v.reshape(B * H, S, hd))
    np.testing.assert_allclose(np.asarray(out.reshape(B * H, S, hd)),
                               np.asarray(o_ref), atol=3e-5, rtol=3e-5)


def test_model_ssd_chunked_vs_oracle():
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(12)
    B, S, H, P, G, N = 2, 64, 4, 16, 1, 16
    xh = jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, S, H)))
    a_log = jnp.zeros((H,))
    Bm = jax.random.normal(jax.random.fold_in(key, 2), (B, S, G, N)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, G, N)) * 0.5
    y, state = ssd_chunked(xh, dt, a_log, Bm, Cm, 16)
    A = -jnp.exp(a_log)
    dA = (dt * A[None, None]).transpose(0, 2, 1).reshape(B * H, S)
    xb = (xh * dt[..., None]).transpose(0, 2, 1, 3).reshape(B * H, S, P)
    Bo = jnp.repeat(Bm, H, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    Co = jnp.repeat(Cm, H, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, N)
    y_ref, st_ref = ref.reference_ssd(xb, dA, Bo, Co)
    np.testing.assert_allclose(
        np.asarray(y.transpose(0, 2, 1, 3).reshape(B * H, S, P)),
        np.asarray(y_ref), atol=3e-5, rtol=3e-5)
    # final states must match too (decode handoff correctness)
    np.testing.assert_allclose(
        np.asarray(state.transpose(0, 1, 3, 2).reshape(B * H, N, P)),
        np.asarray(st_ref), atol=3e-5, rtol=3e-5)


def test_model_ssd_chunked_grads_finite_at_long_chunks():
    """At mamba2's chunk of 256 the decay above the diagonal overflows
    exp(); the mask must keep it out of the gradient."""
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(13)
    B, S, H, P, G, N = 1, 256, 2, 8, 1, 8
    xh = jax.random.normal(key, (B, S, H, P))
    dt = jnp.full((B, S, H), 2.0)          # strong decay: cum spans >> 88
    Bm = jax.random.normal(jax.random.fold_in(key, 1), (B, S, G, N))
    Cm = jax.random.normal(jax.random.fold_in(key, 2), (B, S, G, N))

    def loss(xh, dt, Bm, Cm):
        y, state = ssd_chunked(xh, dt, jnp.zeros((H,)), Bm, Cm, 256)
        return jnp.sum(y ** 2) + jnp.sum(state ** 2)
    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(xh, dt, Bm, Cm)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
