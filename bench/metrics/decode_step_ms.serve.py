"""decode_step_ms.serve: ``ServingEngine.generate``'s decode_s over
(generated - 1), mean over the window's serve steps (host clock)."""
from bench import records


def read(run):
    s = records.served(run)
    if not s:
        return None
    return 1e3 * sum(x["out"]["decode_s"] / (x["gen_len"] - 1) for x in s) \
        / len(s)
