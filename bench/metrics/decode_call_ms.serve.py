"""decode_call_ms.serve: device milliseconds of one decode call. Of the
jitted modules whose executions start inside the program's own
``serve.decode`` host spans (``ServingEngine.generate``), the one that
takes the most device time there (``jit_decode_step``; sampling's small
modules also start there), and the mean of its executions. Prefill's
calls start inside ``serve.prefill`` and are not counted."""
from bench import trace_reduce as TR


def read(run):
    if run.device_trace is None:
        return None
    events = run.device_trace["events"]
    win = [(h.start, h.end) for h in TR.host_spans(events)
           if h.name == "serve.decode"]
    inside = [e for e in TR.modules(events)
              if any(s <= e.start <= t for s, t in win)]
    if not inside:
        return None
    # XLA suffixes a module's name with an id: group by the base name
    calls = {}
    for e in inside:
        calls.setdefault(e.name.split("(")[0], []).append(e.dur)
    busiest = max(calls.values(), key=sum)
    return 1e3 * sum(busiest) / len(busiest)
