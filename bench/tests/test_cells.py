"""Each cell end to end at a tiny size on the CPU, through the same
harness, kinds, references and metrics as on the chip (the look for a chip
skipped), with the cell's own limits; and with the timed path broken
underneath, once for each fault the cell can have, ``correct`` comes out
false."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness as H
from bench.tests.tiny import tiny_cell, tiny_run

CELLS = [w["name"] for w in H.load_spec()["workloads"]]
FAULTS = {"finetune": ["stale_state", "half_batch"], "serve": ["token"]}


def execute(cell, **kw):
    return H.execute(tiny_run(cell, **kw), check_device=False)


@pytest.mark.parametrize("name", CELLS)
def test_cell_correct_at_tiny_size(name):
    cell = tiny_cell(name)
    res = execute(cell)
    assert res["correct"], res["checked"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "checked"
    assert set(res["checked"]) == set(cell.limits)


@pytest.mark.parametrize("name,fault", [
    (n, f) for n in CELLS
    for f in FAULTS[H.load_cell(n).traffic["kind"]]])
def test_broken_timed_path_is_not_correct(name, fault):
    res = execute(tiny_cell(name), fault=fault)
    assert not res["correct"], res["checked"]


def run_cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = run_cli(H.ROOT)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(H.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(H.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_per_layer_metrics(name, monkeypatch):
    """A ``--trace 1`` run reports per-layer metrics; on the CPU the trace
    has no TPU plane, so the device metrics find nothing and are left out
    rather than read as 0. (The CPU gets a stand-in peak so that
    ``train_mfu`` can be read; the table itself refuses a CPU.)"""
    from bench import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny_cell(name, trace_seconds=0.5)
    res = execute(cell, trace=True)
    got = set(res["metrics"])
    device = {m["name"] for m in cell.per_layer
              if m["source"] == "device_trace"}
    assert got == {m["name"] for m in cell.per_layer} - device
    assert res["device"]["busy_s"] == 0.0 and "breakdown" in res


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference with float8 matmul operands in the program's place
    fails the cell's limits (a serve mix at the cell's depth: see
    ``tiny.DEEP``)."""
    from bench import calibrate, compare
    cell = tiny_cell(name, deep=True)
    run = tiny_run(cell)
    H.execute(run, check_device=False)
    low = calibrate.control(cell, run)
    assert not H.judge(compare.with_limits(low, cell.limits), 0), low
