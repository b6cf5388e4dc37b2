"""serve_p50_ms: the median latency of the requests due in the window,
from each one's due time to its answer (host clock)."""
from bench.metrics._latency import percentile_ms


def read(rec):
    return percentile_ms(rec, 0.50)
