"""Helpers the metric readers share: what a run's records hold, in the
units the metrics report."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def p95(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 95)) if values else None


def served(run) -> List[dict]:
    return [s for s in run.records.get("served", []) if s["ok"]]


def ttft_ms(run) -> List[float]:
    """Per request: due time to first token (the batch's first token)."""
    return [1e3 * (s["out"]["t_first"] - s["due_abs"])
            for s in served(run) for _ in range(s["requests"])]


def tpot_ms(run) -> List[float]:
    return [1e3 * s["out"]["decode_s"] / (s["gen_len"] - 1)
            for s in served(run) for _ in range(s["requests"])]


def step_spans(run, step: str):
    return [sp for tr in run.trees for sp in tr.steps if sp.step == step]


def trace_spans(run, name: str):
    """The trace's ``bench:<name>`` host spans, or None without a trace."""
    if run.device_trace is None:
        return None
    return run.device_trace["spans"].get(f"bench:{name}") or None


def mean_live_positions(run) -> Optional[float]:
    """Mean number of cache positions live in a decode-path call over all
    calls of the window's served workflows (prefill included: it runs
    through the same call)."""
    calls = live = 0
    for s in served(run):
        n = s["prompt_len"] + s["gen_len"] - 1
        calls += n
        live += n * (n + 1) / 2
    return live / calls if calls else None


def decode_call_s(run) -> Optional[float]:
    """Device seconds of one decode-path call: the mean execution of the
    jitted module that takes the most device time inside serve spans."""
    from bench import trace_reduce as TR
    win = trace_spans(run, "serve")
    if not win:
        return None
    inside = [e for e in TR.modules(run.device_trace["events"])
              if any(s <= e.start and e.end <= t for s, t in win)]
    if not inside:
        return None
    by = TR.time_by_name(inside)
    # XLA suffixes a module's name with an id: group by the base name
    base = lambda n: n.split("(")[0]
    totals = {}
    for n, v in by.items():
        totals[base(n)] = totals.get(base(n), 0.0) + v
    name = max(totals, key=totals.get)
    calls = [e.dur for e in inside if base(e.name) == name]
    return sum(calls) / len(calls)
