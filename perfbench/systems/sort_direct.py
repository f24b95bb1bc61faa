"""Direct calls of `repro.sort.sort` in a closed loop.

The configuration gives the spec, the chips and the keys per chip; the
traffic mix gives the key distribution, how many distinct inputs the loop
cycles through, and which calls are compared. Inputs are made on the
device, sharded over the chips the way a deployment holds them. Each call
is timed on the host from entering `sort()` to its return (the front
door, before anything blocks) and to `block_until_ready` on the result's
`shards` and `counts`. The window ends with the first call that completes
past `--seconds`, so every call in it is whole.
"""
from __future__ import annotations

import time

import numpy as np

from harness import reference, traffic


class State:
    pass


def _spec_and_sharding(cell, devices):
    """The spec, and the sharding the inputs are placed with: a 1-D mesh
    over the chips in the order `jax.make_mesh` gives them, which is the
    order the sort's own mesh takes (a mesh in `jax.devices()` order is
    refused by the launch on a 2x2 host)."""
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec

    from repro.sort import SortSpec

    spec = SortSpec(**cell.config["spec"])
    mesh = jax.make_mesh((len(devices),), (spec.axis_name,), devices=devices,
                         axis_types=(AxisType.Auto,))
    return spec, NamedSharding(mesh, PartitionSpec(spec.axis_name))


def setup(cell, seed: int, devices, seconds: float) -> State:
    import jax

    import repro.sort as rs

    cfg, mix = cell.config, cell.traffic
    st = State()
    st.rs = rs
    st.spec, sharding = _spec_and_sharding(cell, devices)
    st.chips = len(devices)
    st.n = int(cfg["keys_per_chip"]) * st.chips
    st.inputs = [traffic.device_keys(seed, j, st.n, mix, sharding=sharding)
                 for j in range(int(mix["inputs"]))]
    for j in range(int(mix["warmup_calls"])):
        out = rs.sort(st.inputs[j % len(st.inputs)], st.spec)
        jax.block_until_ready((out.shards, out.counts))
        del out
    st.check_at = traffic.checked_calls(seed, mix)
    return st


def measure(st: State, seconds: float, tracer) -> dict:
    import jax

    calls, overflow, kept, errors = [], [], {}, []
    out = None
    with tracer:
        t0 = time.perf_counter()
        i = 0
        while True:
            x = st.inputs[i % len(st.inputs)]
            out = None   # the last output is freed before the next call
            t_start = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("perfbench.front_door"):
                    out = st.rs.sort(x, st.spec)
                t_return = time.perf_counter()
                with jax.profiler.TraceAnnotation("perfbench.block"):
                    jax.block_until_ready((out.shards, out.counts))
                ok = True
            except Exception as e:   # a failed call is counted, not fatal
                errors.append(f"call {i}: {type(e).__name__}: {e}")
                out, ok, t_return = None, False, time.perf_counter()
            t_done = time.perf_counter()
            calls.append((st.n, t_start - t0, t_return - t0, t_done - t0, ok))
            overflow.append(out.overflow if ok else None)
            if ok and i in st.check_at:
                kept[i] = (out.shards, out.counts)
            i += 1
            if t_done - t0 >= seconds:
                break
    # compared calls the window did not reach: the last call stands in
    last = len(calls) - 1
    st.check_at = sorted({min(j, last) for j in st.check_at})
    if last in st.check_at and out is not None:
        kept[last] = (out.shards, out.counts)
    del out
    st.kept = kept
    ovf = [None if o is None else int(np.max(np.asarray(o))) for o in overflow]
    failed = sum(1 for c, o in zip(calls, ovf) if not c[4] or o)
    return {"calls": calls, "overflow": ovf, "attempted": len(calls),
            "failed": failed, "keys_per_chip": st.n // st.chips,
            "notes": [f"calls {len(calls)}, failed {failed}, "
                      f"compared calls {st.check_at}"] + errors[:5]}


def collect(st: State) -> dict:
    """Host copies of the compared calls' outputs and inputs; then the
    device state is released."""
    n_inputs = len(st.inputs)
    compared = []
    for i in st.check_at:
        got = st.kept.get(i)
        if got is None:
            compared.append((i, None, None))
            continue
        shards, counts = (np.asarray(a) for a in got)
        compared.append((i, shards, counts))
    inputs = {i % n_inputs: np.asarray(st.inputs[i % n_inputs])
              for i in st.check_at}
    st.inputs = st.kept = None
    return {"compared": compared, "inputs": inputs, "n_inputs": n_inputs}


def outputs(collected: dict) -> list:
    """(input keys, gathered output or None) of each compared call."""
    pairs = []
    for i, shards, counts in collected["compared"]:
        keys = collected["inputs"][i % collected["n_inputs"]]
        got = (None if shards is None else np.concatenate(
            [shards[s, :counts[s]] for s in range(shards.shape[0])]))
        pairs.append((keys, got))
    return pairs


def check(collected: dict, produce=None) -> reference.Comparison:
    """Compare each compared call with `np.sort` of its input. `produce`,
    when given, stands in for the program (the control)."""
    cmp = reference.Comparison()
    mismatched, unanswered = 0, 0
    want_of = {}
    for keys, got in outputs(collected):
        if produce is not None:
            got = produce(keys)
        if got is None:
            unanswered += 1
            continue
        k = id(keys)
        if k not in want_of:
            want_of[k] = reference.reference_sort(keys)
        mismatched += reference.mismatched_keys(got, want_of[k])
    cmp.add("mismatched_keys", mismatched, 0)
    cmp.add("unanswered", unanswered, 0)
    return cmp
