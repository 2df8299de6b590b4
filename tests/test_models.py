"""Per-arch smoke tests: REDUCED same-family configs, one forward/train step
on CPU, asserting output shapes + no NaNs (task spec requirement)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_arch, reduced
from repro.models import transformer as T
from repro.training import train as TR


def _reduced(aid):
    spec = get_arch(aid)
    cfg = reduced(spec.model).replace(param_dtype="float32",
                                      compute_dtype="float32")
    return cfg, spec.train


def _batch(cfg, B=2, S=32):
    b = {"tokens": jnp.ones((B, S), jnp.int32) * 3,
         "targets": jnp.ones((B, S), jnp.int32) * 5}
    if cfg.family == "encdec":
        b["frames"] = jnp.ones((B, cfg.enc_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        b["patches"] = jnp.ones((B, cfg.num_patches, cfg.d_model), jnp.float32)
    return b


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_train_step_smoke(aid):
    cfg, tcfg = _reduced(aid)
    state = TR.init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(TR.make_train_step(cfg, tcfg))
    state, m = step(state, _batch(cfg))
    assert jnp.isfinite(m["loss"])
    assert int(state["step"]) == 1
    # a second step must also be finite (optimizer state exercised)
    state, m2 = step(state, _batch(cfg))
    assert jnp.isfinite(m2["loss"])


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_forward_shapes(aid):
    cfg, tcfg = _reduced(aid)
    params = T.init_lm(jax.random.PRNGKey(1), cfg)
    B, S = 2, 32
    b = _batch(cfg, B, S)
    logits, aux = T.apply_lm(params, cfg, b["tokens"],
                             frames=b.get("frames"), patches=b.get("patches"))
    S_out = S + (cfg.num_patches if cfg.family == "vlm" else 0)
    assert logits.shape == (B, S_out, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_decode_step(aid):
    cfg, tcfg = _reduced(aid)
    params = T.init_lm(jax.random.PRNGKey(2), cfg)
    B = 2
    caches = T.init_caches(cfg, B, 16, jnp.float32)
    tok = jnp.ones((B, 1), jnp.int32)
    fn = jax.jit(lambda p, t, c, i: T.apply_lm_decode(p, cfg, t, c, i))
    logits, caches = fn(params, tok, caches, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))
    logits2, _ = fn(params, tok, caches, jnp.int32(1))
    assert not bool(jnp.any(jnp.isnan(logits2)))


def test_param_counts_sane():
    for aid in ARCH_IDS:
        cfg = get_arch(aid).model
        c = cfg.param_counts()
        assert c["total"] >= c["active"] > 0
    assert get_arch("deepseek-v3-671b").model.param_counts()["total"] > 5e11
    assert get_arch("mamba2-370m").model.param_counts()["total"] < 6e8


# (arch, prompt length, seed, atol, rtol): each architecture's own sizes
# and tolerance
DECODE_CASES = {
    "dense": ("stablelm-1.6b", 8, 3, 2e-2, 2e-2),       # GQA
    "ssm": ("mamba2-370m", 16, 5, 2e-2, 2e-2),          # chunked vs recurrent
    "mla": ("deepseek-v3-671b", 8, 7, 2e-2, 0.0),       # absorbed-matmul decode
    "moe": ("olmoe-1b-7b", 8, 7, 2e-2, 0.0),
    "hybrid": ("zamba2-1.2b", 8, 7, 2e-2, 0.0),
    "gqa_kv_lt_heads": ("mistral-nemo-12b", 8, 7, 2e-2, 0.0),
    "encdec": ("whisper-large-v3", 8, 7, 2e-2, 0.0),
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_decode_matches_forward(case):
    """Sequential decode reproduces the full forward's logits: at every
    position |full - decode| - rtol * |decode| < atol."""
    aid, S, seed, atol, rtol = DECODE_CASES[case]
    cfg, _ = _reduced(aid)
    params = T.init_lm(jax.random.PRNGKey(seed), cfg)
    B = 1
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, 100)
    kwargs = {}
    if cfg.family == "encdec":
        kwargs["frames"] = jnp.ones((B, cfg.enc_seq, cfg.d_model)) * 0.1
    full_logits, _ = T.apply_lm(params, cfg, toks, **kwargs)
    caches = T.init_caches(cfg, B, S, jnp.float32)
    if cfg.family == "encdec":
        # populate cross-attention caches from the encoder output
        he = T._encode(params, cfg, kwargs["frames"])
        Se = he.shape[1]
        hd, KH = cfg.head_dim, cfg.num_kv_heads

        def fill(lp):
            k = (he @ lp["cross_attn"]["wk"]).reshape(B, Se, KH, hd)
            v = (he @ lp["cross_attn"]["wv"]).reshape(B, Se, KH, hd)
            return {"k": k.transpose(0, 2, 1, 3), "v": v.transpose(0, 2, 1, 3)}
        caches["cross"] = jax.vmap(fill)(params["dec_layers"])
    outs = []
    for i in range(S):
        lg, caches = T.apply_lm_decode(params, cfg, toks[:, i:i + 1], caches,
                                       jnp.int32(i))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    err = float(jnp.max(jnp.abs(full_logits - dec) - rtol * jnp.abs(dec)))
    assert err < atol, err


def test_paper_workload_bonus_archs():
    """§VI workload models (nanoGPT, ViT) train on CPU (bonus configs)."""
    from repro.configs.paper_workload import BONUS_ARCHS
    from repro.configs import reduced
    for aid, spec in BONUS_ARCHS.items():
        cfg = reduced(spec.model).replace(param_dtype="float32",
                                          compute_dtype="float32")
        state = TR.init_train_state(cfg, spec.train, jax.random.PRNGKey(0))
        step = jax.jit(TR.make_train_step(cfg, spec.train))
        state, m = step(state, _batch(cfg))
        assert jnp.isfinite(m["loss"]), aid
