"""queue_wait_ms.serve: mean over the window's serve steps of their
``queue-wait`` segment (ObsCollector spans, host clock): the time a ready
serve step waited for the engine's one in-flight slot."""
from bench import records


def read(run):
    spans = records.step_spans(run, "serve")
    if not spans:
        return None
    return 1e3 * sum(sp.seg_total("queue-wait") for sp in spans) / len(spans)
