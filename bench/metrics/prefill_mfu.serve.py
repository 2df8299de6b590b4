"""prefill_mfu.serve: the model operations of one one-call prefill
(``bench/flops_mla_moe.py``, at the padded length ``max_len``) over its
device time x chips x the chip's bf16 peak, in %. The device time is the
mean execution of the ``jit_prefill`` modules that overlap the harness's
``bench:serve`` spans (a prefill's start can fall just outside the
program's ``serve.prefill`` span on the device's clock)."""
from bench import flops_mla_moe as F
from bench import peaks, records
from bench import trace_reduce as TR


def read(run):
    win = records.trace_spans(run, "serve")
    if not win:
        return None
    calls = [e.dur for e in TR.modules(run.device_trace["events"])
             if e.name.split("(")[0] == "jit_prefill"
             and any(e.start < t and s < e.end for s, t in win)]
    if not calls:
        return None
    m, t = run.cell.config["model"], run.cell.traffic
    flops = F.prefill_flops(m, t["requests_per_workflow"], t["max_len"])
    peak = peaks.peaks(run.device["kind"])["bf16_flops"]
    return 100.0 * flops / (sum(calls) / len(calls) * run.cell.chips * peak)
