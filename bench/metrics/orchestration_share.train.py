"""orchestration_share.train: share (%) of the fine-tune workflows'
makespan in no step's ``compute`` segment (ObsCollector spans, host
clock): admission, scheduling, hand-offs between steps."""


def read(run):
    if not run.records.get("workflows") or not run.trees:
        return None
    span = sum(tr.makespan_s for tr in run.trees)
    compute = sum(sp.seg_total("compute") for tr in run.trees
                  for sp in tr.steps)
    return 100.0 * (1.0 - compute / span) if span > 0 else None
