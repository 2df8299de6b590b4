"""Batched serving engine: cache-backed prefill + greedy/temperature decode.

Wraps the model's decode step (KV cache for attention families, O(1)
recurrent state for SSM/hybrid) behind one request-batch API, the same
for every family. The ``serve_step`` this engine jits is the same
function the ``decode_32k`` / ``long_500k`` dry-run cells lower at
production scale. A prompt of the families in ``T.PREFILL_FAMILIES`` fills
the caches in one call; the other families feed it through the decode
step, one call a prompt token.

For family moe the prefill call also returns how many prompt rows each
held expert of each expert layer computed. They come back with the
logits and are counted in ``METRICS`` (docs/observability.md).

The prefill call consumes the caches it is handed (donated). A decode
call consumes the attention caches: it writes only the new position of
each, in place, and returns the caches the next call takes. It rewrites a
recurrent state whole into a new buffer, and does not take it donated: a
donated state would first be copied.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.obs.metrics import MetricsRegistry
from repro.models import transformer as T

span = jax.profiler.TraceAnnotation

# the serving engines' counters, in this process
METRICS = MetricsRegistry("serving")
# buckets of a prefill's largest held-expert load over the mean held load
LOAD_RATIO_BUCKETS = (1.0, 1.1, 1.25, 1.5, 2.0, 3.0, 5.0, 10.0)


@dataclass
class GenerationResult:
    tokens: list
    prefill_s: float
    decode_s: float
    tokens_per_s: float
    # (B, V) float32 logits at the last prompt position, from which the
    # first generated token is sampled
    prompt_logits: Optional[jax.Array] = None
    # device calls the prefill took: 1 through ``jit_prefill``, else one
    # decode call a prompt token
    prefill_calls: int = 0
    # family moe: the prefill's rows per held expert, [expert layer][expert]
    expert_load: Optional[list] = None


class ServingEngine:
    def __init__(self, cfg, params, *, max_len: int = 512,
                 cache_dtype=jnp.float32):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.cache_dtype = cache_dtype

        # named, so that its module reads ``jit_decode_step`` in a trace;
        # kv: the attention caches, donated; states: the recurrent states
        # (``T.split_states``)
        def decode_step(p, t, kv, states, i):
            return T.apply_lm_decode(p, cfg, t, {**kv, **states}, i)

        self._step = jax.jit(decode_step, donate_argnums=(2,))
        self._prefill = None
        if T.has_one_call_prefill(cfg):
            # (logits, caches, rows per held expert of each expert layer,
            # or None without expert layers)
            def prefill(p, t, n, c):
                return T.apply_lm_prefill(p, cfg, t, n, c)

            # module ``jit_prefill``; one program per (batch, max_len): the
            # prompt is padded to ``_prefill_len`` and its length is traced
            self._prefill = jax.jit(prefill, donate_argnums=(3,))
            self._prefill_len = T.prefill_len(cfg, max_len)

    def decode(self, tokens, caches, index: int):
        """One ``jit_decode_step`` call: tokens (B,1) at position ``index``
        -> (logits (B,1,V), the new caches). It consumes the attention
        caches it is handed."""
        kv, states = T.split_states(caches)
        return self._step(self.params, tokens, kv, states, jnp.int32(index))

    def generate(self, prompts: jax.Array, gen_len: int,
                 temperature: float = 0.0, seed: int = 0) -> GenerationResult:
        """prompts: (B, P) int32 token batch -> greedy/temp decode.

        Its phases are host spans on the profiler's clock:
        ``serve.cache_init``, ``serve.prefill``, ``serve.decode`` (one
        ``serve.token`` per decode call inside it) and ``serve.fetch``."""
        B, P = prompts.shape
        if not 1 <= P <= self.max_len - gen_len:
            raise ValueError(f"a prompt of {P} tokens and {gen_len} generated "
                             f"do not fit max_len {self.max_len}")
        with span("serve.cache_init"):
            caches = T.init_caches(self.cfg, B, self.max_len,
                                   self.cache_dtype)
        key = jax.random.PRNGKey(seed)

        with span("serve.prefill"):
            t0 = time.time()
            if self._prefill is not None:
                # padded on the host: a device pad would compile once per P
                padded = np.zeros((B, self._prefill_len), np.int32)
                padded[:, :P] = np.asarray(prompts)
                logits, caches, rows = self._prefill(
                    self.params, padded, np.int32(P), caches)
                prefill_calls = 1
            else:
                rows = None
                for i in range(P):          # prefill via the decode path
                    logits, caches = self.decode(prompts[:, i:i + 1],
                                                 caches, i)
                prefill_calls = P
            jax.block_until_ready((logits, caches, rows))
            prefill_s = time.time() - t0
        prompt_logits = logits[:, -1]

        def sample(lg, k):
            if temperature <= 0:
                return jnp.argmax(lg[:, -1], -1)[:, None]
            return jax.random.categorical(k, lg[:, -1] / temperature)[:, None]

        with span("serve.decode"):
            t0 = time.time()
            tok = sample(logits, key)
            out = [tok]
            for i in range(P, P + gen_len - 1):
                with span("serve.token"):
                    logits, caches = self.decode(tok, caches, i)
                    key = jax.random.fold_in(key, i)
                    tok = sample(logits, key)
                out.append(tok)
            gen = jax.block_until_ready(jnp.concatenate(out, axis=1))
            decode_s = time.time() - t0
        with span("serve.fetch"):
            tokens = gen.tolist()
            expert_load = (count_expert_load(rows) if rows is not None
                           else None)
        return GenerationResult(
            tokens=tokens, prefill_s=prefill_s, decode_s=decode_s,
            tokens_per_s=B * gen.shape[1] / max(decode_s, 1e-9),
            prompt_logits=prompt_logits, prefill_calls=prefill_calls,
            expert_load=expert_load)


def count_expert_load(load) -> list:
    """Count one prefill's rows per (expert layer, held expert) in
    ``METRICS``: the counter ``prefill_expert_rows`` and the histogram
    ``prefill_expert_load_max`` of the largest load over the mean."""
    load = np.asarray(load)
    for (layer, expert), n in np.ndenumerate(load):
        METRICS.counter("prefill_expert_rows", layer=str(layer),
                        expert=str(expert)).inc(int(n))
    mean = load.mean()
    if mean > 0:
        METRICS.histogram("prefill_expert_load_max",
                          buckets=LOAD_RATIO_BUCKETS).observe(
            float(load.max() / mean))
    return load.tolist()

