"""ttft_p95_ms: 95th percentile over the window's requests of the time from
the request's due time to its first token (host clock): queue wait, the
serve step's set-up and prefill through the decode path. A request that
fails counts as missing (``failed``)."""
from bench import records


def read(run):
    return records.p95(records.ttft_ms(run))
