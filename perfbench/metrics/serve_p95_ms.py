"""serve_p95_ms: 95th percentile of the latencies `serve_p50_ms` reads,
over the same requests (nearest rank). A failed, refused or unanswered
request counts as infinitely late."""
from harness.stats import percentile


def read(run):
    return percentile(run.data["latency_ms"], 0.95)
