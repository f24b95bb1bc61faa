"""front_door_ms.records: mean host time of a `sort_records()` call from
entering it to its return, before the benchmark blocks on the result:
the plan, the launch's dispatch and the decode, with no host sync."""


def read(run):
    spans = [c[2] - c[1] for c in run.data["calls"] if c[4]]
    return 1e3 * sum(spans) / len(spans) if spans else None
