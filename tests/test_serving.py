import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.array import ArrayImpl

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench.tests.test_mla_moe import cut  # noqa: E402
from repro.configs import get_arch, reduced  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import engine as E  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402


@pytest.mark.parametrize("aid", ["stablelm-1.6b", "mamba2-370m",
                                 "zamba2-1.2b"])
def test_generate_batched(aid):
    cfg = reduced(get_arch(aid).model).replace(param_dtype="float32",
                                               compute_dtype="float32")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_len=32)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 100)
    res = eng.generate(prompts, gen_len=8)
    assert len(res.tokens) == 2 and len(res.tokens[0]) == 8
    assert res.tokens_per_s > 0


def test_temperature_sampling_differs():
    cfg = reduced(get_arch("stablelm-1.6b").model).replace(
        param_dtype="float32", compute_dtype="float32")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, max_len=32)
    prompts = jnp.ones((1, 4), jnp.int32)
    a = eng.generate(prompts, gen_len=10, temperature=1.5, seed=1)
    b = eng.generate(prompts, gen_len=10, temperature=1.5, seed=2)
    assert a.tokens != b.tokens          # different seeds -> different samples
    g = eng.generate(prompts, gen_len=10, temperature=0.0)
    g2 = eng.generate(prompts, gen_len=10, temperature=0.0)
    assert g.tokens == g2.tokens         # greedy is deterministic


# ---------------------------------------------------------------------------
# one-call prefill (``T.apply_lm_prefill``) against the per-token decode loop
# ---------------------------------------------------------------------------

PREFILL_ARCHS = ["stablelm-1.6b", "mamba2-370m"]
MAX_LEN = 40          # past the reduced ssm_chunk (16), not a multiple of it


def f32_model(aid):
    cfg = reduced(get_arch(aid).model).replace(param_dtype="float32",
                                               compute_dtype="float32")
    return cfg, T.init_lm(jax.random.PRNGKey(0), cfg)


def rel_gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("length", [1, 2, 21, MAX_LEN])
@pytest.mark.parametrize("aid", PREFILL_ARCHS)
def test_prefill_matches_the_decode_loop(aid, length):
    """Last-position logits and every cache the decode step reads next
    (KV below ``length``; SSM state and conv windows) equal those of
    ``length`` decode calls, within float32 rounding of a different
    summation order. Lengths 1 and 2 are shorter than the conv window;
    21 is not a multiple of ssm_chunk once padded."""
    cfg, params = f32_model(aid)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, length), 0,
                                 cfg.vocab_size)
    step = jax.jit(lambda p, t, c, i: T.apply_lm_decode(p, cfg, t, c, i))
    want_caches = T.init_caches(cfg, 2, MAX_LEN, jnp.float32)
    for i in range(length):
        want, want_caches = step(params, prompts[:, i:i + 1], want_caches,
                                 jnp.int32(i))

    S = T.prefill_len(cfg, MAX_LEN)
    assert S == {"dense": MAX_LEN, "ssm": 48}[cfg.family]
    padded = jnp.zeros((2, S), jnp.int32).at[:, :length].set(prompts)
    got, got_caches, rows = jax.jit(
        lambda p, t, n, c: T.apply_lm_prefill(p, cfg, t, n, c))(
        params, padded, jnp.int32(length),
        T.init_caches(cfg, 2, MAX_LEN, jnp.float32))

    assert got.shape == want.shape == (2, 1, cfg.padded_vocab)
    assert rows is None                   # no layer holds experts
    assert rel_gap(got, want) < 1e-4
    names = {"dense": ("k", "v"),
             "ssm": ("state", "conv_x", "conv_B", "conv_C")}[cfg.family]
    assert set(got_caches["layers"]) == set(names)
    for name in names:
        g, w = got_caches["layers"][name], want_caches["layers"][name]
        assert g.shape == w.shape and g.dtype == w.dtype, name
        if name in ("k", "v"):                # (L, B, KH, max_len, hd)
            g, w = g[..., :length, :], w[..., :length, :]
        assert rel_gap(g, w) < 1e-4, name


@pytest.mark.parametrize("aid", PREFILL_ARCHS + ["olmoe-1b-7b"])
def test_generate_prefills_in_one_call_and_matches_the_decode_loop(aid):
    cfg, params = f32_model(aid)
    eng = ServingEngine(cfg, params, max_len=32)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 7), 0,
                                 cfg.vocab_size)
    got = eng.generate(prompts, gen_len=5)
    assert got.prefill_calls == 1
    step = jax.jit(lambda p, t, c, i: T.apply_lm_decode(p, cfg, t, c, i))
    caches = T.init_caches(cfg, 2, 32, jnp.float32)
    for i in range(7):
        logits, caches = step(params, prompts[:, i:i + 1], caches,
                              jnp.int32(i))
    assert rel_gap(got.prompt_logits, logits[:, -1]) < 1e-4
    want = [jnp.argmax(logits[:, -1], -1)[:, None]]
    for i in range(7, 11):
        logits, caches = step(params, want[-1], caches, jnp.int32(i))
        want.append(jnp.argmax(logits[:, -1], -1)[:, None])
    assert got.tokens == jnp.concatenate(want, axis=1).tolist()


@pytest.mark.parametrize("aid", PREFILL_ARCHS)
def test_one_prefill_program_serves_every_prompt_length(aid):
    cfg, params = f32_model(aid)
    eng = ServingEngine(cfg, params, max_len=MAX_LEN)
    for P in (3, 26):
        prompts = jax.random.randint(jax.random.PRNGKey(P), (2, P), 0,
                                     cfg.vocab_size)
        res = eng.generate(prompts, gen_len=4)
        assert res.prefill_calls == 1
        assert len(res.tokens[0]) == 4
    assert eng._prefill._cache_size() == 1


@pytest.mark.parametrize("aid", ["zamba2-1.2b"])
def test_other_families_prefill_through_the_decode_step(aid):
    """hybrid has no one-call prefill: a prompt of P tokens takes P decode
    calls."""
    cfg, params = f32_model(aid)
    eng = ServingEngine(cfg, params, max_len=16)
    assert cfg.family not in T.PREFILL_FAMILIES and eng._prefill is None
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0,
                                 cfg.vocab_size)
    res = eng.generate(prompts, gen_len=3)
    assert res.prefill_calls == 5
    assert len(res.tokens[0]) == 3


@pytest.mark.parametrize("prompt_len", [0, 29])
def test_generate_refuses_prompts_that_do_not_fit(prompt_len):
    """An empty prompt has no last position to prefill from, and a long
    one leaves the cache no room for the generated tokens."""
    cfg, params = f32_model("stablelm-1.6b")
    eng = ServingEngine(cfg, params, max_len=32)
    with pytest.raises(ValueError, match="do not fit"):
        eng.generate(jnp.ones((2, prompt_len), jnp.int32), gen_len=4)


# the three cells' families at the tests' size: stablelm, mamba2, and
# DeepSeek-V3's expert-parallel share (4 of 8 experts held)
FETCH_CASES = {
    "dense": lambda: reduced(get_arch("stablelm-1.6b").model),
    "ssm": lambda: reduced(get_arch("mamba2-370m").model),
    "moe": lambda: cut()[0],
}


@pytest.mark.parametrize("family", list(FETCH_CASES))
def test_generate_fetches_nothing_per_token(family, monkeypatch):
    """No decode call's output goes to the host inside ``serve.token``:
    ``generate`` fetches the tokens (and a prefill's rows per held expert)
    once, after the decode loop."""
    cfg = FETCH_CASES[family]()
    assert cfg.family == family
    eng = ServingEngine(cfg, T.init_lm(jax.random.PRNGKey(9), cfg),
                        max_len=24)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                 cfg.vocab_size)
    eng.generate(prompts, 3)                   # compiles every program

    in_token, fetched = [False], []
    value = ArrayImpl._value

    def fetch(self):
        fetched.append(in_token[0])
        return value.fget(self)

    asarray = np.asarray

    def np_asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            fetched.append(in_token[0])
        return asarray(a, *args, **kw)

    class Span(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            super().__init__(name, **kw)
            self.token = name == "serve.token"

        def __enter__(self):
            in_token[0] = self.token
            return super().__enter__()

        def __exit__(self, *exc):
            in_token[0] = False
            return super().__exit__(*exc)

    monkeypatch.setattr(ArrayImpl, "_value", property(fetch))
    monkeypatch.setattr(np, "asarray", np_asarray)
    monkeypatch.setattr(E, "span", Span)
    out = eng.generate(prompts, 5)
    assert len(out.tokens[0]) == 5
    assert (out.expert_load is not None) == (family == "moe")
    assert fetched and not any(fetched)     # fetches, none per token
