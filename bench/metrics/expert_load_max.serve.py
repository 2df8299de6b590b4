"""expert_load_max.serve: how unevenly a prefill loads the experts this chip
holds: per one-call prefill, the largest rows of one held expert in one
expert layer over the mean rows of a held expert, averaged over the
process's prefills (the window's and set-up's one warm-up workflow). Read
from the program's counter (``repro.serving.engine.METRICS``, histogram
``prefill_expert_load_max``); None where the program keeps no such
counter."""


def read(run):
    try:
        from repro.serving import engine
    except ImportError:
        return None
    metrics = getattr(engine, "METRICS", None)
    if metrics is None:
        return None
    h = metrics.snapshot().get("prefill_expert_load_max")
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"]
