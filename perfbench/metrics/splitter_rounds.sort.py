"""splitter_rounds.sort: HSS splitter rounds run per `sort()` call, the
mean over the chips. A round that runs ranks its sample with one launch
of the histogram kernel (`repro.runtime.spans.HISTOGRAM_KERNEL`) and a
converged round skips it, so the kernel's launches in the HSS launch
per call count the rounds the paper bounds (`SplitterStats.rounds_used`).
Where the program's kernel bears no such name nothing is read."""
from harness import hss_launch, scopes


def read(run):
    names, trace = scopes.table(), run.trace
    calls = len(run.data["calls"])
    if names is None or trace is None or not trace.devices or not calls:
        return None
    launches = [scopes.ops_started(trace, d, "^" + names.HISTOGRAM_KERNEL,
                                   hss_launch.MODULE)
                for d in trace.devices]
    mean = sum(launches) / len(launches)
    return mean / calls if mean > 0 else None
