"""decode_mfu.serve: the whole decode-path call's share (%) of the chip's
bf16 peak: its operations (``bench/flops.py``) over its device time in the
trace times the peak."""
from bench import flops, peaks, records


def read(run):
    t_call = records.decode_call_s(run)
    live = records.mean_live_positions(run)
    if t_call is None or live is None:
        return None
    m, t = run.cell.config["model"], run.cell.traffic
    work = flops.decode_flops(m, t["requests_per_workflow"], live)
    return 100.0 * work / (t_call * peaks.peaks(run.device["kind"])[
        "bf16_flops"])
