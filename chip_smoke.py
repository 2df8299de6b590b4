"""Bring-up check on the chip: the workflow path at published widths.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # four TPU chips: sharded training

One chip: mamba2-370m at its published widths and full depth (bf16, weights
drawn from a seed) runs as one fine-tune-and-serve workflow through
``couler.workflow`` and a ``LocalEngine`` with its default options:

    init   the train state, from the seed
    train  TRAIN_STEPS steps at BATCH x SEQ tokens of ``synthetic_batches``
    serve  N_REQUESTS requests through ``ServingEngine`` with the trained
           parameters (PROMPT_LEN prompt tokens, GEN_LEN generated)
    check  the serving engine's logits at the last prompt position (its
           one-call prefill) against ``T.apply_lm``'s full forward on the
           same prompts

It fails unless the run succeeded with no step retried or speculated, the
loss is finite and lower at the last step than at the first, and the two
logit paths agree within LOGIT_RTOL.

Four chips: stablelm-1.6b at published widths and depth takes FOUR_STEPS
``pure_fsdp`` train steps on a 2x2 mesh through ``launch.train``, whose
state fits only across the mesh. The same config cut to CUT_LAYERS layers
takes the same steps on the 2x2 mesh and on device 0 alone, and the losses
of the two must agree within LOSS_RTOL.

Exits non-zero, and prints no result, when JAX finds no TPU or any phase
fails. The last line of standard output is one JSON object naming the
device. Outputs go to ``out/chip_smoke/``, which each run clears first.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402

from repro.configs import get_arch                             # noqa: E402
from repro.core import couler                                  # noqa: E402
from repro.core.engines.local import LocalEngine               # noqa: E402
from repro.core.ir import Resources                            # noqa: E402
from repro.data.pipeline import synthetic_batches              # noqa: E402
from repro.launch.compile_cache import use_compile_cache       # noqa: E402
from repro.launch.train import train as sharded_train          # noqa: E402
from repro.models import transformer as T                      # noqa: E402
from repro.serving.engine import ServingEngine                 # noqa: E402
from repro.training import train as TR                         # noqa: E402

OUT = ROOT / "out" / "chip_smoke"
SEED = 0

ARCH = "mamba2-370m"
BATCH, SEQ, TRAIN_STEPS = 8, 2048, 10
N_REQUESTS, PROMPT_LEN, GEN_LEN = 8, 128, 32
# Largest serve-vs-forward logit difference allowed, as a share of the
# largest logit. Both paths run in bf16, which keeps 8 significant bits
# (2^-8 ~ 0.4% per rounding). The limit was set when the serve path carried
# the recurrent state token by token, rounding other intermediates than the
# full forward's chunked SSD scan; its prefill now runs that scan over the
# prompt padded to the engine's max_len. That gap grew with depth, about as
# sqrt(layers): on a CPU at cut widths it was 1.7% at 2 layers and 8% at 48
# (d_model 64), 2.8% at 8 layers with d_model 1024, and 4e-6 at 48 layers
# in float32, so it is rounding alone. A wrong state or cache hand-off
# differs by the order of the logits themselves.
LOGIT_RTOL = 0.15

FOUR_ARCH = "stablelm-1.6b"
FOUR_MESH = (2, 2)
FOUR_STRATEGY = "pure_fsdp"
FOUR_BATCH, FOUR_SEQ, FOUR_STEPS = 4, 2048, 3
CUT_LAYERS = 4
# The mesh and the single device sum the same bf16 products in different
# orders (sharded matmuls reduce across chips), and AdamW's first updates
# are close to lr * sign(grad), which turns such last-bit differences in
# near-zero gradients into whole-step differences of a few weights. The
# loss moves by much less than 1% for that; a wrong sharding or a dropped
# shard moves it by far more.
LOSS_RTOL = 0.01

# a step on the chip declares it, so the engine never races a second copy
ON_CHIP = Resources(gpu=1)


def phase(name: str, **fields) -> None:
    """One line per phase on standard output, before the final result."""
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def model_config(arch: str = ARCH):
    spec = get_arch(arch)
    return spec.model, spec.train


def jit_train_step(cfg, tcfg):
    """The jitted train step the workflow's train phase runs; the state it
    is given is donated, so its buffers hold the updated state."""
    return jax.jit(TR.make_train_step(cfg, tcfg), donate_argnums=(0,))


def train_batch_shapes(cfg, batch: int = BATCH, seq: int = SEQ):
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return {"tokens": tok, "targets": tok}


# ----------------------------------------------------------------------
# one chip: the fine-tune-and-serve workflow's steps
# ----------------------------------------------------------------------

def init_step(cfg, tcfg, seed: int):
    init = jax.jit(lambda k: TR.init_train_state(cfg, tcfg, k))
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    compiled = init.lower(key).compile()
    t1 = time.perf_counter()
    state = jax.block_until_ready(compiled(key))
    phase("init", compile_s=t1 - t0, execute_s=time.perf_counter() - t1)
    return state


def train_step(state, cfg, tcfg, seed: int, steps: int = TRAIN_STEPS,
               batch: int = BATCH, seq: int = SEQ):
    batches = [{k: jnp.asarray(v) for k, v in b.items()}
               for b in synthetic_batches(batch, seq, cfg.vocab_size,
                                          seed=seed, n=steps)]
    t0 = time.perf_counter()
    compiled = jit_train_step(cfg, tcfg).lower(state, batches[0]).compile()
    t1 = time.perf_counter()
    losses = []
    for b in batches:
        state, metrics = compiled(state, b)
        losses.append(metrics["loss"])
    jax.block_until_ready(state)
    t2 = time.perf_counter()
    losses = [float(x) for x in losses]
    mem = compiled.memory_analysis()
    phase("train", compile_s=t1 - t0, execute_s=t2 - t1,
          step_s=(t2 - t1) / steps, tokens=steps * batch * seq,
          argument_bytes=mem.argument_size_in_bytes,
          temp_bytes=mem.temp_size_in_bytes)
    phase("train", losses=losses)
    return {"params": state["params"], "losses": losses}


def serve_step(trained, cfg, seed: int, n: int = N_REQUESTS,
               prompt_len: int = PROMPT_LEN, gen_len: int = GEN_LEN):
    prompts = jnp.asarray(next(synthetic_batches(
        n, prompt_len, cfg.vocab_size, seed=seed, n=1))["tokens"])
    eng = ServingEngine(cfg, trained["params"], max_len=prompt_len + gen_len)
    t0 = time.perf_counter()
    cold = eng.generate(prompts, gen_len)       # compiles prefill and decode
    t1 = time.perf_counter()
    warm = eng.generate(prompts, gen_len)
    t2 = time.perf_counter()
    phase("serve", compile_s=(t1 - t0) - (t2 - t1), execute_s=t2 - t1,
          prefill_s=warm.prefill_s, decode_s=warm.decode_s,
          decode_tokens_per_s=warm.tokens_per_s, requests=n)
    return {"prompts": prompts, "prompt_logits": warm.prompt_logits,
            "tokens": warm.tokens, "deterministic": cold.tokens == warm.tokens}


def check_step(trained, served, cfg):
    fwd = jax.jit(lambda p, t: T.apply_lm(p, cfg, t)[0][:, -1])
    t0 = time.perf_counter()
    compiled = fwd.lower(trained["params"], served["prompts"]).compile()
    t1 = time.perf_counter()
    ref = jax.block_until_ready(compiled(trained["params"], served["prompts"]))
    t2 = time.perf_counter()
    diff = float(jnp.max(jnp.abs(served["prompt_logits"] - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    phase("check", compile_s=t1 - t0, execute_s=t2 - t1,
          max_abs_logit_diff=diff, max_abs_logit=scale,
          rtol=LOGIT_RTOL)
    return {"max_abs_logit_diff": diff, "max_abs_logit": scale}


def build_workflow(cfg, tcfg, *, seed: int = SEED, **sizes):
    """The fine-tune-and-serve DAG. Device steps are not cacheable: the
    engine's cache key pickles every input, which for the train state
    would copy it to the host on every step."""
    train_kw = {k: v for k, v in sizes.items()
                if k in ("steps", "batch", "seq")}
    serve_kw = {k: v for k, v in sizes.items()
                if k in ("n", "prompt_len", "gen_len")}
    with couler.workflow("chip-smoke") as wf:
        state = couler.run_step(init_step, cfg, tcfg, seed,
                                step_name="init", cacheable=False,
                                resources=ON_CHIP)
        trained = couler.run_step(train_step, state, cfg, tcfg, seed,
                                  step_name="train", cacheable=False,
                                  resources=ON_CHIP, **train_kw)
        served = couler.run_step(serve_step, trained, cfg, seed + 1,
                                 step_name="serve", cacheable=False,
                                 resources=ON_CHIP, **serve_kw)
        couler.run_step(check_step, trained, served, cfg,
                        step_name="check", cacheable=False,
                        resources=ON_CHIP)
    return wf


def run_one_chip(cfg, tcfg, out_dir: Path, *, seed: int = SEED,
                 **sizes) -> dict:
    """Run the workflow on a default ``LocalEngine`` and check it; raises
    ``SmokeFailure`` naming every check that failed. The run's record goes
    to ``out_dir``."""
    wf = build_workflow(cfg, tcfg, seed=seed, **sizes)
    eng = LocalEngine()
    try:
        run = eng.submit(wf)
    finally:
        eng.close()
    run.persist(str(out_dir))
    failures = []
    if not run.succeeded():
        failures += [f"step {k} {r.status.value}: {r.error}"
                     for k, r in run.steps.items() if r.error]
        raise SmokeFailure(failures or [f"run {run.status}"])
    for k, r in run.steps.items():
        if r.attempts != 1 or r.speculative:
            failures.append(f"step {k}: attempts={r.attempts} "
                            f"speculative={r.speculative}")
    losses = run.artifacts["train:out"]["losses"]
    if not all(math.isfinite(x) for x in losses):
        failures.append(f"non-finite loss: {losses}")
    elif not losses[-1] < losses[0]:
        failures.append(f"loss did not drop: {losses[0]} -> {losses[-1]}")
    served = run.artifacts["serve:out"]
    if not served["deterministic"]:
        failures.append("greedy decode differs between two runs")
    chk = run.artifacts["check:out"]
    if not chk["max_abs_logit_diff"] <= LOGIT_RTOL * chk["max_abs_logit"]:
        failures.append(
            f"decode logits differ from the full forward by "
            f"{chk['max_abs_logit_diff']}, more than {LOGIT_RTOL} of "
            f"{chk['max_abs_logit']}")
    if failures:
        raise SmokeFailure(failures)
    return {"arch": cfg.name, "losses": losses,
            "step_s": {k: r.duration() for k, r in run.steps.items()},
            **chk}


# ----------------------------------------------------------------------
# four chips: sharded training through launch.train
# ----------------------------------------------------------------------

def run_four_chips(cfg, tcfg, out_dir: Path, *, arch: str = FOUR_ARCH,
                   mesh=FOUR_MESH, strategy: str = FOUR_STRATEGY,
                   cut_layers: int = CUT_LAYERS, seed: int = SEED,
                   **sizes) -> dict:
    """Three chained workflow steps, one program on the chips at a time:
    the full config on the mesh, then the cut config on the mesh and on
    device 0 alone."""
    kw = dict(arch=arch, strategy=strategy, seed=seed,
              batch=sizes.get("batch", FOUR_BATCH),
              seq=sizes.get("seq", FOUR_SEQ),
              steps=sizes.get("steps", FOUR_STEPS), log_every=1)
    cut = cfg.replace(num_layers=cut_layers)
    with couler.workflow("chip-smoke-four") as wf:
        full = couler.run_step(sharded_train, cfg, tcfg, mesh_shape=mesh,
                               step_name="full-mesh", cacheable=False,
                               resources=Resources(gpu=4), **kw)
        on_mesh = couler.run_step(sharded_train, cut, tcfg, mesh_shape=mesh,
                                  step_name="cut-mesh", cacheable=False,
                                  resources=Resources(gpu=4), **kw)
        alone = couler.run_step(sharded_train, cut, tcfg, mesh_shape=(1, 1),
                                step_name="cut-device0", cacheable=False,
                                resources=ON_CHIP, **kw)
        couler.set_dependencies(on_mesh, [full])
        couler.set_dependencies(alone, [on_mesh])
    eng = LocalEngine()
    try:
        run = eng.submit(wf)
    finally:
        eng.close()
    run.persist(str(out_dir))
    if not run.succeeded():
        raise SmokeFailure([f"step {k} {r.status.value}: {r.error}"
                            for k, r in run.steps.items() if r.error]
                           or [f"run {run.status}"])
    res = {k: run.artifacts[f"{k}:out"]
           for k in ("full-mesh", "cut-mesh", "cut-device0")}
    for k, losses in res.items():
        phase(k, seconds=run.steps[k].duration(), losses=losses)
    rel = max(abs(a - b) / abs(b)
              for a, b in zip(res["cut-mesh"], res["cut-device0"]))
    phase("four-chips", cut_layers=cut_layers,
          max_rel_loss_diff_mesh_vs_device0=rel, rtol=LOSS_RTOL)
    failures = []
    for k, losses in res.items():
        if len(losses) != kw["steps"] or not all(map(math.isfinite, losses)):
            failures.append(f"{k}: losses {losses}")
    if not rel <= LOSS_RTOL:
        failures.append(f"cut config: mesh and device 0 losses differ by "
                        f"{rel} relative, more than {LOSS_RTOL}")
    if failures:
        raise SmokeFailure(failures)
    return {"arch": arch, "mesh": list(mesh), "strategy": strategy,
            "losses": res, "max_rel_loss_diff": rel}


class SmokeFailure(RuntimeError):
    def __init__(self, failures):
        super().__init__("; ".join(failures))
        self.failures = failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training phase on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    use_compile_cache()
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)
    try:
        if args.four_chips:
            result = run_four_chips(*model_config(FOUR_ARCH), OUT)
        else:
            result = run_one_chip(*model_config(ARCH), OUT)
    except SmokeFailure as e:
        for f in e.failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    stats = devices[0].memory_stats() or {}
    phase("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
          bytes_limit=stats.get("bytes_limit"))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    (OUT / "result.json").write_text(json.dumps(
        {"device": device, **result}, indent=1, default=str))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
