"""device_idle_share.train: 1 - device busy / length over the union of the
train steps' ``bench:train`` spans in the trace, in %."""
from bench import records, trace_reduce


def read(run):
    win = records.trace_spans(run, "train")
    if not win:
        return None
    share = trace_reduce.idle_share(run.device_trace["events"], win)
    return None if share is None else 100.0 * share
