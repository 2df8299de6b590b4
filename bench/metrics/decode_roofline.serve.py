"""decode_roofline.serve: the least time a decode-path call could take,
its least bytes (``bench/flops.py``: bf16 weights once, the live cache or
recurrent state at bf16, bf16 logits) over the chip's HBM bandwidth, as a
share (%) of the call's device time in the trace."""
from bench import flops, peaks, records


def read(run):
    t_call = records.decode_call_s(run)
    live = records.mean_live_positions(run)
    if t_call is None or live is None:
        return None
    m, t = run.cell.config["model"], run.cell.traffic
    least = flops.decode_bytes(m, t["requests_per_workflow"], live) \
        / peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / t_call
