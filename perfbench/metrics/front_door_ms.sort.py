"""front_door_ms.sort: mean host time of a `sort()` call from entering it
to its return, before the benchmark blocks on the result. It holds the
adapter's duplicate-detection sort and the host syncs that wait on it."""


def read(run):
    spans = [c[2] - c[1] for c in run.data["calls"] if c[4]]
    return 1e3 * sum(spans) / len(spans) if spans else None
