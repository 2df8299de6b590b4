"""Fine-tune workflows, closed loop: the traffic kind ``finetune``.

Each workflow is three steps submitted through ``couler.workflow`` to one
``LocalEngine``:

    batches  host: ``steps_per_workflow`` batches of ``batch`` x ``seq``
             tokens, and one held-out batch, drawn from the seed
    train    device: that many AdamW steps of ``training.train``'s step,
             from the state the previous workflow left (donated)
    eval     device: the loss of the held-out batch

Set-up builds the state and the compiled steps once, and runs the first
workflow through the same call as the window: its first ``checked_steps``
steps are the ones the check compares with the reference. The window then
launches workflows back to back until ``--seconds`` have passed.

Check: the reference (``bench/reference``, float32) starts from the same
seed and takes the same first steps on the same batches. Compared: each
step's loss, the norm of each leaf of the first gradient as the optimizer
got it (the program's AdamW first moment after one step, over 1 - beta1),
and the norm of each leaf's change after the checked steps, each by its
gap to the reference (see ``bench/compare.py``).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

ON_CHIP_GPUS = 1


def make_batches(vocab: int, batch: int, seq: int, n: int, seed: int,
                 index: int) -> Dict[str, Any]:
    """The host step: ``n`` train batches and one held-out batch, every row
    different, from (seed, workflow index)."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, index])
    toks = rng.integers(0, vocab, (n + 1, batch, seq + 1), dtype=np.int32)
    rows = [{"tokens": t[:, :-1], "targets": t[:, 1:]} for t in toks]
    return {"train": rows[:n], "eval": rows[n]}


class Trainer:
    """The train state and compiled steps one process keeps across
    workflows (the user's fine-tune job)."""

    def __init__(self, cfg, tcfg, key, batch: int, seq: int,
                 fault: Optional[str] = None):
        import jax
        import jax.numpy as jnp
        from repro.training import train as TR

        self.tokens_per_step = batch * seq
        init = jax.jit(lambda k: TR.init_train_state(cfg, tcfg, k))
        self.state = jax.block_until_ready(init(key))
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        shapes = {"tokens": tok, "targets": tok}
        step = TR.make_train_step(cfg, tcfg)
        if fault == "stale_state":       # returns its state unchanged
            inner = step
            step = lambda s, b: (s, inner(s, b)[1])
        elif fault == "half_batch":      # drops half the rows
            inner = step
            step = lambda s, b: inner(s, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], b))
        self.train = jax.jit(step, donate_argnums=(0,)).lower(
            self.state, shapes).compile()
        self.eval = jax.jit(TR.make_eval_step(cfg, tcfg)).lower(
            self.state["params"], shapes).compile()


def train_step(batches: Dict[str, Any], trainer: Trainer,
               observe=None) -> Dict[str, Any]:
    """The workflow's train step: AdamW steps on each batch, the state
    carried in ``trainer``. ``observe(i, state)`` is called before step i
    and after the last (set-up's first workflow only)."""
    import jax
    import jax.numpy as jnp
    with jax.profiler.TraceAnnotation("bench:train"):
        state, losses = trainer.state, []
        for i, b in enumerate(batches["train"]):
            if observe is not None:
                observe(i, state)
            b = {k: jnp.asarray(v) for k, v in b.items()}
            state, m = trainer.train(state, b)
            losses.append(m["loss"])
        trainer.state = state
        jax.block_until_ready(state)
    if observe is not None:
        observe(len(batches["train"]), state)
    return {"losses": [float(x) for x in losses],
            "tokens": len(losses) * trainer.tokens_per_step}


def eval_step(batches: Dict[str, Any], trained: Dict[str, Any],
              trainer: Trainer) -> float:
    import jax
    import jax.numpy as jnp
    with jax.profiler.TraceAnnotation("bench:eval"):
        b = {k: jnp.asarray(v) for k, v in batches["eval"].items()}
        return float(trainer.eval(trainer.state["params"], b)["loss"])


def build_workflow(run, trainer: Trainer, index: int, observe=None):
    from repro.core import couler
    from repro.core.ir import Resources
    t, m = run.cell.traffic, run.cell.config["model"]
    chip = Resources(gpu=ON_CHIP_GPUS)
    with couler.workflow(f"finetune-{index}") as wf:
        b = couler.run_step(make_batches, m["vocab_size"], t["batch"],
                            t["seq"], t["steps_per_workflow"], run.seed,
                            index, step_name="batches", cacheable=False)
        kw = {"observe": observe} if observe is not None else {}
        tr = couler.run_step(train_step, b, trainer, step_name="train",
                             cacheable=False, resources=chip, **kw)
        couler.run_step(eval_step, b, tr, trainer, step_name="eval",
                        cacheable=False, resources=chip)
    return wf


class Readings:
    """The program's numbers for the check, taken in set-up's first
    workflow: losses, first-gradient leaf norms, change leaf norms."""

    def __init__(self, checked: int, beta1: float):
        self.checked, self.beta1 = checked, beta1
        self.p0 = None
        self.grad: Dict[str, float] = {}
        self.change: Dict[str, float] = {}

    def __call__(self, i: int, state) -> None:
        import jax
        from bench.reference import common as C
        if i == 0:
            self.p0 = jax.tree.map(lambda a: a.copy(), state["params"])
        if i == 1:
            mu = C.leaf_norms(state["opt"]["mu"])
            self.grad = {k: v / (1.0 - self.beta1) for k, v in mu.items()}
        if i == self.checked:
            self.change = C.leaf_change_norms(state["params"], self.p0)
            self.p0 = None


def first_workflow(run, trainer: Trainer, engine) -> Dict[str, Any]:
    """Run workflow 0 through the window's own call, reading the program's
    numbers for the check from its first steps."""
    from bench.harness import phase
    t = run.cell.traffic
    readings = Readings(t["checked_steps"], run.cell.config["train"]["beta1"])
    first = engine.submit(build_workflow(run, trainer, 0, readings))
    if not first.succeeded():
        raise RuntimeError(f"set-up workflow failed: "
                           f"{[r.error for r in first.steps.values()]}")
    losses = first.artifacts["train:out"]["losses"]
    phase("setup", first_workflow_s=first.wall_time_s, losses=losses)
    return {"losses": losses[: t["checked_steps"]], "grad": readings.grad,
            "change": readings.change}


def setup(run):
    from repro.core import couler
    from repro.core.engines.local import LocalEngine
    from bench.harness import phase, seed_key
    cfg, tcfg = run.cell.model_config()
    t = run.cell.traffic
    t0 = time.time()
    trainer = Trainer(cfg, tcfg, seed_key(run.seed), t["batch"], t["seq"],
                      fault=run.fault)
    engine = LocalEngine(max_inflight_steps=t["max_inflight_steps"])
    obs = couler.observe(engine)
    phase("setup", build_s=time.time() - t0)
    run.records["program"] = first_workflow(run, trainer, engine)
    return {"trainer": trainer, "engine": engine, "obs": obs}


def window(run, st) -> None:
    """Closed loop, one client: workflows back to back until the window's
    seconds have passed; the window ends with the last of them."""
    engine, trainer = st["engine"], st["trainer"]
    steps = run.cell.traffic["steps_per_workflow"]
    wfs = []
    index = 1
    while time.time() - run.t_window < run.seconds:
        start = time.time()
        r = engine.submit(build_workflow(run, trainer, index))
        ok = r.succeeded()
        out = r.artifacts.get("train:out") if ok else None
        wfs.append({"start": start, "end": time.time(), "ok": ok,
                    "tokens": out["tokens"] if ok else 0,
                    "losses": out["losses"] if ok else [],
                    "run_id": r.run_id})
        run.attempted += steps
        run.failed += 0 if ok else steps
        index += 1
    run.t_window_end = wfs[-1]["end"] if wfs else time.time()
    run.records["workflows"] = wfs
    run.records["train_steps"] = sum(steps for w in wfs if w["ok"])
    ids = {w["run_id"] for w in wfs}
    run.trees = [tr for tr in st["obs"].trees() if tr.run_id in ids]


def release(st) -> None:
    st["engine"].close()
    st["trainer"].state = None
    st.clear()


def check(run) -> Dict[str, Dict[str, float]]:
    """The reference takes the checked steps from the same seed on the same
    batches; returns each compared number with its limit."""
    from bench import compare
    from bench.harness import phase
    from bench.reference import common as C
    t0 = time.time()
    ref = reference_steps(run, C.Numerics())
    phase("reference", seconds=time.time() - t0, losses=ref["losses"])
    run.records["reference"] = ref
    return compare.training(run.records["program"], ref, run.cell.limits)


def reference_steps(run, num) -> Dict[str, Any]:
    """The reference's readings after the checked steps: losses, first
    clipped gradient's leaf norms, leaf norms of the change."""
    import jax
    from bench.harness import seed_key
    from bench.reference import common as C
    cell, t, seed = run.cell, run.cell.traffic, run.seed
    ref, m, tr = cell.reference, cell.config["model"], cell.config["train"]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: ref.init(m, k))(seed_key(seed))
        p0 = jax.tree.map(lambda a: a.copy(), params)
        opt = C.adamw_init(params)
        step = jax.jit(C.make_train_step(
            lambda p, b: ref.loss(p, m, b, num), tr, rows=1),
            donate_argnums=(0, 1))
        batches = make_batches(m["vocab_size"], t["batch"], t["seq"],
                               t["steps_per_workflow"], seed, 0)["train"]
        losses, grad = [], {}
        for i, b in enumerate(batches[: t["checked_steps"]]):
            params, opt, loss, gnorm = step(params, opt, b)
            losses.append(float(loss))
            if i == 0:
                grad = {jax.tree_util.keystr(k): float(v) for k, v in
                        jax.tree_util.tree_flatten_with_path(gnorm)[0]}
        change = C.leaf_change_norms(params, p0)
    return {"losses": losses, "grad": grad, "change": change}
