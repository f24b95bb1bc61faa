"""sort_keys_per_s: keys of every `sort()` call that completed without
overflow in the window, over the window's length (host clock; the window
ends when its last call's result is ready on the device)."""


def read(run):
    calls, overflow = run.data["calls"], run.data["overflow"]
    keys = sum(c[0] for c, o in zip(calls, overflow) if c[4] and not o)
    return keys / run.window_s
