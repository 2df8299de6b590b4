"""train_step_ms: the train steps' ``compute`` segments (each ends in
``block_until_ready``) over the number of steps they took (host clock)."""
from bench import records


def read(run):
    steps = run.records.get("train_steps")
    spans = records.step_spans(run, "train")
    if not steps or not spans:
        return None
    return 1e3 * sum(sp.seg_total("compute") for sp in spans) / steps
