"""decode_host_ms.serve: host milliseconds per generated token, the mean
duration of the program's ``serve.token`` spans in the trace: one decode
call's dispatch, the key's ``fold_in`` and the sampling, each dispatched
without waiting on the device. Beside ``decode_call_ms.serve`` it says
whether decode waits on the host. Read in the traced run, so it holds the
tracer's own host cost."""
from bench import trace_reduce as TR


def read(run):
    if run.device_trace is None:
        return None
    d = [h.dur for h in TR.host_spans(run.device_trace["events"])
         if h.name == "serve.token"]
    return 1e3 * sum(d) / len(d) if d else None
