"""detect_ms.sort: device time per `sort()` call of the front door's
duplicate detection, the mean over the chips. The detection's sort,
compare and any run as one program, the module `jit_detect_duplicates`
(`repro.runtime.spans.DETECT_MODULE`); where the program has no such
module (it ran the detection as eager ops) nothing is read."""
MODULE = r"^jit_detect_duplicates$"


def read(run):
    trace = run.trace
    calls = len(run.data["calls"])
    if trace is None or not trace.devices or not calls:
        return None
    secs = [trace.op_seconds(d, [r""], MODULE) for d in trace.devices]
    mean = sum(secs) / len(secs)
    return 1e3 * mean / calls if mean > 0 else None
