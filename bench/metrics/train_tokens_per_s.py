"""train_tokens_per_s: train tokens of every fine-tune workflow launched in
the window, over the time from the window's start to the end of the last
(host clock)."""


def read(run):
    wfs = run.records.get("workflows")
    if not wfs:
        return None
    return sum(w["tokens"] for w in wfs) / (wfs[-1]["end"] - run.t_window)
