"""Batch-inference workflows, open loop: the traffic kind ``serve``.

Each arrival is one workflow of two steps submitted through
``couler.workflow`` to one ``LocalEngine`` whose ``max_inflight_steps``
the traffic file sets (1: two serve steps on one chip would each allocate
a cache):

    prompts  host: ``requests_per_workflow`` prompts of one length P
    serve    device: ``ServingEngine.generate``, ``gen_len`` greedy tokens

Arrivals: the gaps are the quantiles of a gamma distribution with mean
1 / ``rate_per_s`` and coefficient of variation ``arrival_cv`` (1 is
Poisson), and P the quantiles of a log-uniform distribution over
[``prompt_min``, ``prompt_max``], each in one fixed order: every run offers
the same work at the same times, and the seed draws the prompts' tokens and
the weights. (With an order drawn from the seed, the order alone moved the
p95 time to first token by 45-48%: which long prompt queues behind which.)
A request's time to first token runs from its due time, so queue wait
counts.

Check: after the window, ``checked_workflows`` finished workflows drawn
from the seed, the one with the longest prompt among them. The reference
runs once over each prompt followed by its served tokens; each served
token's gap below the reference's best logit at its position is compared.
"""
from __future__ import annotations

import concurrent.futures as cf
import math
import time
from typing import Any, Dict, List

import numpy as np

GRACE_S = 60.0          # how long past the window a due request may finish
ORDER_SEED = 0          # the one order of a mix's gaps and prompt lengths
WARM_INDEX = 1 << 30    # prompt index of set-up's warm-up workflow


def schedule(t: Dict[str, Any], seconds: float) -> List[Dict]:
    """Due times (seconds from the window's start) and prompt lengths."""
    from scipy import stats
    n = max(1, round(t["rate_per_s"] * seconds))
    q = (np.arange(n) + 0.5) / n
    k = 1.0 / t["arrival_cv"] ** 2
    gaps = stats.gamma.ppf(q, k, scale=1.0 / (t["rate_per_s"] * k))
    lo, hi = math.log(t["prompt_min"]), math.log(t["prompt_max"])
    lens = np.rint(np.exp(lo + q * (hi - lo))).astype(int)
    rng = np.random.default_rng(ORDER_SEED)
    gaps, lens = gaps[rng.permutation(n)], lens[rng.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [{"index": i, "due": float(d), "prompt_len": int(p)}
            for i, (d, p) in enumerate(zip(due, lens)) if d < seconds]


def make_prompts(vocab: int, batch: int, prompt_len: int, seed: int,
                 index: int) -> np.ndarray:
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, index, 7])
    return rng.integers(0, vocab, (batch, prompt_len), dtype=np.int32)


class Server:
    """The served weights and the one ``ServingEngine`` of this process."""

    def __init__(self, cfg, key, max_len: int, gen_len: int, vocab: int,
                 fault=None):
        import jax
        from repro.models import transformer as T
        from repro.serving.engine import ServingEngine
        params = jax.block_until_ready(
            jax.jit(lambda k: T.init_lm(k, cfg))(key))
        self.engine = ServingEngine(cfg, params, max_len=max_len)
        self.gen_len, self.vocab, self.fault = gen_len, vocab, fault


def serve_step(prompts: np.ndarray, server: Server) -> Dict[str, Any]:
    """The workflow's serve step: greedy generation for the batch."""
    import jax
    import jax.numpy as jnp
    with jax.profiler.TraceAnnotation("bench:serve"):
        r = server.engine.generate(jnp.asarray(prompts), server.gen_len)
        t_end = time.time()
    tokens = r.tokens
    if server.fault == "token":        # one served token altered
        tokens[0][-1] = (tokens[0][-1] + 1) % server.vocab
    return {"t_first": t_end - r.decode_s, "t_end": t_end,
            "prefill_s": r.prefill_s, "decode_s": r.decode_s,
            "tokens": tokens}


def build_workflow(run, server: Server, index: int, prompt_len: int):
    from repro.core import couler
    from repro.core.ir import Resources
    t, m = run.cell.traffic, run.cell.config["model"]
    with couler.workflow(f"serve-{index}") as wf:
        p = couler.run_step(make_prompts, m["vocab_size"],
                            t["requests_per_workflow"], prompt_len,
                            run.seed, index, step_name="prompts",
                            cacheable=False)
        couler.run_step(serve_step, p, server, step_name="serve",
                        cacheable=False, resources=Resources(gpu=1))
    return wf


def warm_slices(batch: int, prompt_len: int) -> None:
    """The engine's prefill slices its prompt one position at a time; each
    prompt shape lowers those slices once."""
    import jax
    import jax.numpy as jnp
    x = jnp.zeros((batch, prompt_len), jnp.int32)
    jax.block_until_ready([x[:, i:i + 1] for i in range(prompt_len)])


def setup(run):
    from repro.core import couler
    from repro.core.engines.local import LocalEngine
    from bench.harness import phase, seed_key
    cfg, _ = run.cell.model_config()
    t = run.cell.traffic
    t0 = time.time()
    server = Server(cfg, seed_key(run.seed), t["max_len"], t["gen_len"],
                    cfg.vocab_size, fault=run.fault)
    engine = LocalEngine(max_inflight_steps=t["max_inflight_steps"])
    obs = couler.observe(engine)
    sched = schedule(t, run.seconds)
    B = t["requests_per_workflow"]
    for p in sorted({w["prompt_len"] for w in sched} | {t["prompt_max"]}):
        warm_slices(B, p)
    phase("setup", build_s=time.time() - t0, workflows=len(sched))
    # one workflow at the longest prompt compiles the decode step and warms
    # the engine's path; its prompt index is none the window uses
    warm = engine.submit(build_workflow(run, server, WARM_INDEX,
                                        t["prompt_max"]))
    if not warm.succeeded():
        raise RuntimeError(f"set-up workflow failed: "
                           f"{[r.error for r in warm.steps.values()]}")
    out = warm.artifacts["serve:out"]
    phase("setup", warm_workflow_s=warm.wall_time_s,
          prefill_s=out["prefill_s"], decode_s=out["decode_s"])
    return {"server": server, "engine": engine, "obs": obs,
            "schedule": sched}


def window(run, st) -> None:
    """Open loop: each workflow is submitted at its due time, whatever is
    still running; the window ends when the last due request finishes, or
    ``GRACE_S`` after the window's close."""
    engine, server = st["engine"], st["server"]
    t = run.cell.traffic
    B, G = t["requests_per_workflow"], t["gen_len"]
    subs, late = [], []
    for w in st["schedule"]:
        due = run.t_window + w["due"]
        wait = due - time.time()
        if wait > 0:
            time.sleep(wait)
        h = engine.gateway.submit_nowait(
            build_workflow(run, server, w["index"], w["prompt_len"]))
        late.append(time.time() - due)
        subs.append((w, due, h))
    deadline = run.t_window + run.seconds + GRACE_S
    served, trees_ids = [], set()
    for w, due, h in subs:
        try:
            r = h.result(timeout=max(0.0, deadline - time.time()))
            ok = r.succeeded()
        except cf.TimeoutError:
            r, ok = None, False
        out = r.artifacts["serve:out"] if ok else None
        served.append({**w, "due_abs": due, "ok": ok, "out": out,
                       "requests": B, "gen_len": G})
        if r is not None:
            trees_ids.add(r.run_id)
        run.attempted += B
        run.failed += 0 if ok else B
    ends = [s["out"]["t_end"] for s in served if s["ok"]]
    run.t_window_end = max(ends) if ends else time.time()
    run.records["served"] = served
    run.records["generator_late_s"] = late
    run.trees = [tr for tr in st["obs"].trees() if tr.run_id in trees_ids]
    from bench.harness import phase
    phase("generator", workflows=len(subs), completed=len(ends),
          late_max_s=max(late) if late else 0.0,
          late_mean_s=sum(late) / len(late) if late else 0.0)


def release(st) -> None:
    st["engine"].close()
    st["server"].engine.params = None
    st.clear()


def checked_workflows(run) -> List[Dict]:
    done = [s for s in run.records["served"] if s["ok"]]
    if not done:
        return []
    longest = max(done, key=lambda s: (s["prompt_len"], -s["index"]))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([run.seed & 0xFFFFFFFF, run.seed >> 32, 11])
    n = min(len(rest), run.cell.traffic["checked_workflows"] - 1)
    pick = rng.choice(len(rest), size=n, replace=False) if n else []
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(run, workflows: List[Dict], control: bool = False):
    """The widest gap of each checked workflow's served tokens below the
    reference's best logit. With ``control``, the token at each position is
    the one the float8 reference puts first instead of the served one."""
    import jax
    import jax.numpy as jnp
    from bench.harness import seed_key
    from bench.reference import common as C
    cell, t = run.cell, run.cell.traffic
    ref, m = cell.reference, cell.config["model"]
    B, G = t["requests_per_workflow"], t["gen_len"]
    S = t["prompt_max"] + G - 1         # one shape for every prompt length

    def logits_at(params, tokens, start, num):
        h = ref.hidden(params, m, tokens, num)
        idx = start + jnp.arange(G)
        return ref.head(params, num)(h[:, idx])        # (B, G, V)

    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: ref.init(m, k))(seed_key(run.seed))
        exact = jax.jit(lambda p, x, s: logits_at(p, x, s, C.Numerics()))
        low = jax.jit(lambda p, x, s: jnp.argmax(
            logits_at(p, x, s, C.Numerics(control=True)), -1))
        gaps = []
        for w in workflows:
            P = w["prompt_len"]
            prompts = make_prompts(m["vocab_size"], B, P, run.seed,
                                   w["index"])
            served = np.asarray(w["out"]["tokens"], np.int32)     # (B, G)
            seq = np.zeros((B, S), np.int32)
            seq[:, :P] = prompts
            seq[:, P:P + G - 1] = served[:, :-1]
            lg = exact(params, seq, P - 1)
            tok = low(params, seq, P - 1) if control else jnp.asarray(served)
            picked = jnp.take_along_axis(lg, tok[..., None], -1)[..., 0]
            gaps.append(float(jnp.max(jnp.max(lg, -1) - picked)))
    return gaps


def check(run) -> Dict[str, Dict[str, float]]:
    from bench import compare
    from bench.harness import phase
    wfs = checked_workflows(run)
    if not wfs:
        return compare.serving(math.inf, run.cell.limits)
    t0 = time.time()
    run.records["checked"] = wfs
    gaps = reference_gaps(run, wfs)
    phase("reference", seconds=time.time() - t0,
          workflows=[w["index"] for w in wfs],
          prompt_lens=[w["prompt_len"] for w in wfs], gaps=gaps,
          tokens=sum(w["requests"] * w["gen_len"] for w in wfs))
    return compare.serving(max(gaps), run.cell.limits)
