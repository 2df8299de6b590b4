"""moe_decode_roofline.serve: the least time one decode call of DeepSeek-V3's
share could take, its least bytes (``bench/flops_mla_moe.py``: bf16 weights
without the embedding and with the held experts some token is routed to,
the bf16 latent cache at the decode calls' mean live positions, bf16
logits) over the chip's HBM bandwidth, as a share (%) of the call's device
time as ``decode_call_ms.serve`` reads it: the mean execution of the
busiest module among those whose executions start inside the program's
``serve.decode`` spans."""
from bench import flops_mla_moe as F
from bench import harness as H
from bench import peaks, records

decode_call_ms = H.load_module(H.BENCH / "metrics" / "decode_call_ms.serve.py",
                               "decode_call_ms_serve")


def read(run):
    t_call = decode_call_ms.read(run)
    served = records.served(run)
    if t_call is None or not served:
        return None
    m, t = run.cell.config["model"], run.cell.traffic
    # decode call i (P .. P+G-2) reads positions 0..i: P + G/2 on average
    live = sum(s["prompt_len"] + s["gen_len"] / 2 for s in served) \
        / len(served)
    least = F.decode_bytes(m, t["requests_per_workflow"], live) \
        / peaks.peaks(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (t_call / 1e3)
