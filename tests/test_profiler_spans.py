"""The program's own spans on the profiler's clock: the serve step's phases
(``serving/engine.py``) and a step's lifecycle on the gateway and engine
(``couler.*``), read back from a ``jax.profiler`` trace the way the
benchmark's reduction reads them. Without jax imported, ``span`` is a
no-op and imports nothing."""
import glob
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.configs import get_arch, reduced
from repro.core.engines.local import LocalEngine
from repro.core.ir import Job, WorkflowIR
from repro.models import transformer as T
from repro.serving.engine import ServingEngine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def traced(fn, trace_dir):
    """Run ``fn`` under a profiler trace; returns its host events whose
    name is the program's (``serve.*``, ``couler.*``) as
    (line, name, start_ns, end_ns), one line per host thread."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            out += [(i, e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(("serve.", "couler."))]
    return out


def named(events, name):
    return sorted((e for e in events if e[1] == name), key=lambda e: e[2])


def small_engine(max_len=32):
    cfg = reduced(get_arch("mamba2-370m").model).replace(
        param_dtype="float32", compute_dtype="float32")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, ServingEngine(cfg, params, max_len=max_len)


def test_serve_spans_bound_the_phases_of_generate(tmp_path):
    _, eng = small_engine()
    prompts = jnp.ones((2, 5), jnp.int32)
    eng.generate(prompts, gen_len=3)               # compiles outside the trace
    gen_len = 6
    ev = traced(lambda: eng.generate(prompts, gen_len=gen_len), tmp_path)
    init, = named(ev, "serve.cache_init")
    prefill, = named(ev, "serve.prefill")
    decode, = named(ev, "serve.decode")
    fetch, = named(ev, "serve.fetch")
    assert init[3] <= prefill[2] and prefill[3] <= decode[2]
    assert decode[3] <= fetch[2]
    tokens = named(ev, "serve.token")
    assert len(tokens) == gen_len - 1
    assert all(decode[2] <= t[2] and t[3] <= decode[3] for t in tokens)
    # one thread: the serve step's own
    assert len({e[0] for e in ev}) == 1


def test_generate_matches_the_plain_decode_loop():
    """Greedy tokens equal those of a plain loop over the same decode
    function, spans and all. The prompt
    is prefilled in one call, which sums in another order than the loop,
    so its logits agree within float32 rounding, not bit for bit."""
    cfg, eng = small_engine()
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0, 100)
    got = eng.generate(prompts, gen_len=6)
    step = jax.jit(lambda p, t, c, i: T.apply_lm_decode(p, cfg, t, c, i))
    caches = T.init_caches(cfg, 2, 32, jnp.float32)
    for i in range(5):
        logits, caches = step(eng.params, prompts[:, i:i + 1], caches,
                              jnp.int32(i))
    want_logits = logits[:, -1]
    assert float(jnp.max(jnp.abs(got.prompt_logits - want_logits))) <= (
        1e-4 * float(jnp.max(jnp.abs(want_logits))))
    want = [jnp.argmax(logits[:, -1], -1)[:, None]]
    for i in range(5, 10):
        logits, caches = step(eng.params, want[-1], caches, jnp.int32(i))
        want.append(jnp.argmax(logits[:, -1], -1)[:, None])
    assert got.tokens == jnp.concatenate(want, axis=1).tolist()


def two_ready_steps(name):
    wf = WorkflowIR(name)
    for s in ("a", "b"):
        wf.add_job(Job(name=s, fn=lambda: time.sleep(0.05) or 1,
                       cacheable=False))
    return wf


def test_gateway_spans_mark_a_steps_lifecycle(tmp_path):
    eng = LocalEngine(max_inflight_steps=1, enable_speculation=False)
    try:
        assert eng.submit(two_ready_steps("warm")).succeeded()

        def two_workflows():
            assert eng.submit(two_ready_steps("w1")).succeeded()
            time.sleep(0.05)
            assert eng.submit(two_ready_steps("w2")).succeeded()
        ev = traced(two_workflows, tmp_path)
    finally:
        eng.close()
    steps = sorted(named(ev, "couler.step:a") + named(ev, "couler.step:b"),
                   key=lambda e: e[2])
    assert len(steps) == 4                          # one per step run
    waits = named(ev, "couler.queue_wait")
    idle = named(ev, "couler.idle")
    loop_lines = {e[0] for e in waits + idle}
    assert len(loop_lines) == 1                     # the loop thread
    assert not loop_lines & {e[0] for e in steps}   # steps run off it
    # one slot: the second ready step of each workflow waits in
    # couler.queue_wait while the first runs
    for first in (steps[0], steps[2]):
        assert any(w[2] <= first[2] and w[3] >= first[3] for w in waits)
    # between the two submissions no step is in flight: couler.idle
    between = [i for i in idle if steps[1][3] <= i[2] and i[3] <= steps[2][2]]
    assert between and max(i[3] - i[2] for i in between) >= 40e6
    assert all(i[3] <= s[2] or i[2] >= s[3] for i in idle for s in steps)


def test_span_is_a_no_op_without_jax():
    """A host-only workflow runs through the gateway and engine without
    importing jax, and ``span`` is then a plain null context. (``spans``
    is imported first: it imports the gateway, which uses it.)"""
    code = (
        "import contextlib, sys\n"
        "from repro.core.obs.spans import span\n"
        "from repro.core.engines.local import LocalEngine\n"
        "from repro.core.ir import Job, WorkflowIR\n"
        "wf = WorkflowIR('host')\n"
        "wf.add_job(Job(name='a', fn=lambda: 1, cacheable=False))\n"
        "eng = LocalEngine(max_inflight_steps=1)\n"
        "assert eng.submit(wf).succeeded()\n"
        "eng.close()\n"
        "assert isinstance(span('x'), contextlib.nullcontext)\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "print('ok')\n")
    env = {**os.environ, "PYTHONPATH": SRC}
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "ok"
