"""batch_host_ms.serve: mean host time of one batch on the service's
dispatch thread, the `serve.batch` span (`repro.runtime.spans.BATCH`:
stack and pad, the host-to-device copy, the front door's `sort_batched`
call and the per-request gathers), over the batches inside the window.
A program without the span reads nothing."""
from harness import scopes


def read(run):
    names = scopes.table()
    if names is None or run.trace is None:
        return None
    batches = scopes.spans_in_window(run.trace, names.BATCH)
    if not batches:
        return None
    return 1e3 * sum(d for _, d in batches) / len(batches) / 1e9
