"""prefill_ms_per_token.serve: ``ServingEngine.generate``'s prefill_s over
the prompt's length, mean over the window's serve steps (host clock)."""
from bench import records


def read(run):
    s = records.served(run)
    if not s:
        return None
    return 1e3 * sum(x["out"]["prefill_s"] / x["prompt_len"] for x in s) \
        / len(s)
