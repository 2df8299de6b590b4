"""tpot_p95_ms: 95th percentile over the window's requests of (completion
- first token) / (generated - 1) (host clock)."""
from bench import records


def read(run):
    return records.p95(records.tpot_ms(run))
