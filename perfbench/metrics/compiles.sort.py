"""compiles.sort: backend compiles inside the window: the program marks
the end of each (`repro.runtime.spans.COMPILE`, from the compile counter
of `repro.runtime.compile_cache`), eager ops' included. A warm closed
loop should read 0; a program without the counter reads nothing."""
from harness import scopes


def read(run):
    names = scopes.table()
    if names is None or run.trace is None:
        return None
    return len(scopes.spans_in_window(run.trace, names.COMPILE))
