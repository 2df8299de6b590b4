"""BENCHMARK.json and the files it names: every name resolves to its own
file, and every metric is reported where it says it is."""
import re

import pytest

from bench import harness as H

SPEC = H.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
E2E = {m["name"]: m for m in SPEC["end_to_end"]}
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"][1:] == ["bench/run.py"]


def test_names():
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    mod = H.load_module(H.BENCH / "metrics" / f"{metric['name']}.py", "m")
    assert callable(mod.read)
    assert metric["better"] in ("lower", "higher")


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert E2E["setup_s"]["bound"] == 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_and_metrics(cell):
    c = H.load_cell(cell)
    assert c.chips in (1, 4)
    assert "setup_s" in [m["name"] for m in c.end_to_end]
    assert len(c.end_to_end) >= 2 and c.per_layer
    reported = {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        assert m["moves"] in reported, (cell, m["name"])
    assert c.limits and all(v > 0 for v in c.limits.values())
    assert c.config["reduced"] == []
    c.kind, c.reference          # both import


def test_per_layer_metrics_name_their_cells():
    for m in SPEC["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
        assert m["moves"] in E2E
