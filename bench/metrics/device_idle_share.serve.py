"""device_idle_share.serve: 1 - device busy / length over the union of the
serve steps' ``bench:serve`` spans only, so that gaps between arrivals do
not count, in %."""
from bench import records, trace_reduce


def read(run):
    win = records.trace_spans(run, "serve")
    if not win:
        return None
    share = trace_reduce.idle_share(run.device_trace["events"], win)
    return None if share is None else 100.0 * share
