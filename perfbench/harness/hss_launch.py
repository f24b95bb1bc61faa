"""Device time of ops inside the HSS launch, per `sort()` call.

The launch is the driver's jitted `shard_map` program, recorded in the
trace as the module `jit_per_shard`. Ops of the front door's eager
programs (the duplicate-detection sort `jit_sort`, the key-range
reductions, decoding) run in modules of their own and do not match. Ops
inside the splitter loop (`while`, and the `conditional` of each round)
are told from the launch's own by `top_level`."""
from __future__ import annotations

MODULE = r"^jit_per_shard$"


def per_call_s(run, op_patterns, *, top_level=False) -> float | None:
    """Mean over the chips of the matching ops' device seconds, per call
    of the window; None where nothing matched."""
    trace = run.trace
    calls = len(run.data["calls"])
    if trace is None or not trace.devices or not calls:
        return None
    secs = [trace.op_seconds(d, op_patterns, MODULE, top_level)
            for d in trace.devices]
    mean = sum(secs) / len(secs)
    return mean / calls if mean > 0 else None


def per_call_ms(run, op_patterns, *, top_level=False) -> float | None:
    s = per_call_s(run, op_patterns, top_level=top_level)
    return None if s is None else 1e3 * s
