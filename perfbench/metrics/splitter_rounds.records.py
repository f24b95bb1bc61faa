"""splitter_rounds.records: HSS splitter rounds per `sort_records()` call,
the program's own counter (`SplitterStats.rounds_used`, returned with the
result as `repro.runtime.spans.ROUNDS_USED`), the mean over the window's
calls. A program without the counter reads nothing."""


def read(run):
    rounds = run.data.get("rounds")
    return sum(rounds) / len(rounds) if rounds else None
