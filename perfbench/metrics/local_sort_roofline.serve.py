"""local_sort_roofline.serve: the served local sorts against their
roofline. Least time: one read and one write of every completed real
request's keys at peak HBM bandwidth (rows that power-of-two padding
adds are not counted). Device time: the Pallas bitonic sort of the
batched launches, jitted as `local_sort_batched` (rows of up to 2^22
keys), whose module holds nothing else. The front door's detection sort
runs in XLA's `jit_sort` and is not counted; rows above 2^22 keys would
be sorted by that same `jit_sort` program and could not be told apart."""
from harness import roofline

MODULE = r"^jit_local_sort_batched$"


def read(run):
    trace = run.trace
    if trace is None or not trace.devices:
        return None
    secs = [trace.op_seconds(d, [r""], MODULE) for d in trace.devices]
    seconds = sum(secs) / len(secs)
    keys = sum(n for n, ok in zip(run.data["sizes"], run.data["completed"])
               if ok)
    return roofline.share_pct(roofline.sort_bytes(keys), seconds, run.peaks)
