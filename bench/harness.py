"""The benchmark's harness: one run of one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric is found
by its name:

    BENCHMARK.json configs[].file      the configuration as it is run
    bench/traffic/<traffic>.json       the traffic mix; its "kind" names
    bench/kinds/<kind>.py              the generator and the workflow it
                                       submits (set-up, window, check)
    bench/reference/<reference>.py     the plain float32 reference the
                                       configuration file names
    bench/limits/<cell>.json           the limits of the cell's check
    bench/metrics/<metric>.py          one reader per metric

A run: set-up (load, make weights on the device from the seed, compile and
warm up every shape the traffic uses), the measured window, the peak device
memory, the program's state freed, the check against the reference, and one
JSON line on standard output. Set-up and window run in this process, so it
holds the chips throughout.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent
OUT = ROOT / ".bench_out"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, missing file)."""


def load_spec(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import a file found by name (metric names hold dots, so these are
    loaded by path rather than as package members)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclass
class Cell:
    """One cell as BENCHMARK.json and its files describe it."""
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration's file
    traffic: Dict[str, Any]         # the traffic mix's file
    limits: Dict[str, float]        # the check's limits
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def kind(self):
        return importlib.import_module(f"bench.kinds.{self.traffic['kind']}")

    @property
    def reference(self):
        return importlib.import_module(
            f"bench.reference.{self.config['reference']}")

    def model_config(self):
        from repro.configs.base import ModelConfig, TrainConfig
        return (ModelConfig(**self.config["model"]),
                TrainConfig(**self.config["train"]))


def load_cell(name: str, root: Path = ROOT,
              spec: Optional[Dict[str, Any]] = None) -> Cell:
    spec = spec or load_spec(root)
    w = find(spec["workloads"], name, "workload")
    c = find(spec["configs"], w["config"], "config")
    applies = lambda m: "workloads" not in m or name in m["workloads"]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / c["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


@dataclass
class Run:
    """What one run hands from the kind to the metric readers: host-clock
    records, span trees, the reduced trace, and the sizes they need."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float
    fault: Optional[str] = None        # tests and calibration only
    t_window: float = 0.0
    t_window_end: float = 0.0
    setup_s: float = 0.0
    compiles_in_window: int = 0
    attempted: int = 0
    failed: int = 0
    records: Dict[str, Any] = field(default_factory=dict)
    trees: List[Any] = field(default_factory=list)
    device_trace: Optional[Dict[str, Any]] = None
    device: Dict[str, Any] = field(default_factory=dict)
    numbers: Dict[str, Dict[str, float]] = field(default_factory=dict)


def phase(name: str, **fields) -> None:
    """One line per phase on standard error, before the result."""
    print(f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs lowered while it is open (each new shape or jitted
    function lowers once, whether the compile then hits the cache)."""

    def __init__(self):
        self.n = 0
        self.on = False

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.on and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self


class Tracer:
    """A profiler trace of the window's first ``seconds`` (a whole window
    of decode calls would be millions of device events)."""

    def __init__(self, trace_dir: Path, seconds: float):
        import jax
        from bench.trace_reduce import trace_options
        self._lock = threading.Lock()
        self._on = True
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_options())
        self._timer = threading.Timer(seconds, self.stop)
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        import jax
        with self._lock:
            if self._on:
                self._on = False
                jax.profiler.stop_trace()
        self._timer.cancel()


def device_info(need: int) -> Dict[str, Any]:
    """The accelerator JAX found; raises unless it is a TPU with at least
    ``need`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < need:
        raise BenchError(f"needs {need} TPU chip(s); JAX found {len(devs)} "
                         f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_bytes(n: int, key: str) -> Optional[int]:
    """The largest of ``memory_stats()[key]`` over the first ``n`` chips."""
    import jax
    vals = [(d.memory_stats() or {}).get(key) for d in jax.devices()[:n]]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def read_metrics(run: Run, entries: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Each metric's reader, ``bench/metrics/<name>.py``, returns a number
    or None (nothing to read: the metric is left out of the line)."""
    out = {}
    for m in entries:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name'].replace('.', '_')}")
        v = mod.read(run)
        if v is not None:
            if not math.isfinite(v):
                raise BenchError(f"metric {m['name']} read {v}")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def judge(numbers: Dict[str, Dict[str, float]], failed: int) -> bool:
    """Correct when nothing failed and every compared number is within its
    limit."""
    return failed == 0 and bool(numbers) and all(
        math.isfinite(n["value"]) and n["value"] <= n["limit"]
        for n in numbers.values())


def execute(run: Run, *, check_device: bool = True) -> Dict[str, Any]:
    """Set-up, window, check and metrics of one run; returns the result
    line's object. ``check_device=False`` (tests) skips the look for a
    TPU and runs wherever JAX runs."""
    import jax
    cell = run.cell
    if check_device:
        run.device = device_info(cell.chips)
    else:
        d = jax.devices()[0]
        run.device = {"platform": d.platform, "kind": d.device_kind,
                      "count": cell.chips}
    counter = CompileCounter().install()
    kind = cell.kind
    state = kind.setup(run)
    trace_dir = OUT / "trace" / f"{cell.name}-{run.seed}"
    if run.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    counter.on = True
    run.t_window = time.time()
    run.setup_s = run.t_window - run.t_process
    tracer = Tracer(trace_dir, cell.traffic.get("trace_seconds", 10.0)) \
        if run.trace else None
    try:
        with jax.profiler.TraceAnnotation("bench:window"):
            kind.window(run, state)
    finally:
        if tracer is not None:
            tracer.stop()
        counter.on = False
    run.compiles_in_window = counter.n
    run.device["memory_peak_bytes"] = memory_bytes(cell.chips,
                                                   "peak_bytes_in_use")
    # what the deployment holds at the window's close, beside the peak
    phase("window", seconds=run.t_window_end - run.t_window,
          compiles_in_window=run.compiles_in_window,
          memory_peak_bytes=run.device["memory_peak_bytes"],
          memory_in_use_bytes=memory_bytes(cell.chips, "bytes_in_use"),
          attempted=run.attempted, failed=run.failed)
    kind.release(state)
    del state
    gc.collect()
    run.numbers = kind.check(run)
    correct = judge(run.numbers, run.failed)
    if run.trace:
        from bench import trace_reduce
        run.device_trace = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.device["busy_s"] = run.device_trace["busy_s"]
        run.device["window_s"] = run.device_trace["window_s"]
    entries = cell.per_layer if run.trace else cell.end_to_end
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": read_metrics(run, entries),
              "device": run.device}
    if run.trace:
        result["breakdown"] = run.device_trace["breakdown"]
    result["checked"] = {k: {"value": v["value"], "limit": v["limit"]}
                         for k, v in run.numbers.items()}
    return result
