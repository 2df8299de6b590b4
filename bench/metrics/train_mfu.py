"""train_mfu: model FLOPs of a train step (forward and backward from the
shapes, ``bench/flops.py``; recomputation not counted) over the step's
time (``train_step_ms``) times chips times the bf16 peak
(``bench/peaks.py``), in %."""
from bench import flops, peaks, records


def read(run):
    steps = run.records.get("train_steps")
    spans = records.step_spans(run, "train")
    if not steps or not spans:
        return None
    t = run.cell.traffic
    step_s = sum(sp.seg_total("compute") for sp in spans) / steps
    work = flops.train_flops_per_token(run.cell.config["model"], t["seq"]) \
        * t["batch"] * t["seq"]
    peak = peaks.peaks(run.device["kind"])["bf16_flops"]
    return 100.0 * work / (step_s * run.cell.chips * peak)
