"""queue_wait_ms.serve: mean time a request waited in the service's
batcher before its batch was dispatched, over the window's requests, from
the service's own `MetricsRegistry` (reset when the window starts)."""


def read(run):
    return run.data["queue_wait_ms"]
