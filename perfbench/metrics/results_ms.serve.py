"""results_ms.serve: mean host time per batch of the `serve.results`
span (`repro.runtime.spans.RESULTS`): each request's gather, a
device-to-host copy and the scatter that compacts it, over the
`serve.batch` spans inside the window. A program without the spans
reads nothing."""
from harness import scopes


def read(run):
    names = scopes.table()
    if names is None or run.trace is None:
        return None
    batches = scopes.spans_in_window(run.trace, names.BATCH)
    if not batches:
        return None
    results = scopes.spans_in_window(run.trace, names.RESULTS)
    return 1e3 * sum(d for _, d in results) / len(batches) / 1e9
