"""serve_p50_ms: median latency of every request due in the window, from
when it was due until its NumPy result was back (host clock). A failed,
refused or unanswered request counts as infinitely late."""
from harness.stats import percentile


def read(run):
    return percentile(run.data["latency_ms"], 0.50)
