"""serve_p95_ms: the 95th percentile (nearest rank) of the same
latencies; a request never answered or failed ranks above all others
(host clock)."""
from bench.metrics._latency import percentile_ms


def read(rec):
    return percentile_ms(rec, 0.95)
