"""Direct calls of `repro.sort.sort_records` in a closed loop.

The configuration gives the spec, the chips and the records per chip; the
traffic mix gives the record generator, how many distinct inputs the loop
cycles through, and which calls are compared. Inputs are made on the
device as (25, n) uint32 word planes, sharded over the chips on their last
axis, the way a deployment holds them. Each call is timed on the host
from entering `sort_records()` to its return and to `block_until_ready`
on the result's `shards` and `counts`; the window ends with the first
call that completes past `--seconds`. Each call's counters (splitter
rounds, bytes on the wire in the all-to-all and the bytes of records
among them, `RecordSortOutput.counters()`) are read after its end is
timed.
"""
from __future__ import annotations

import time

import numpy as np

from harness import record_reference, reference, traffic


class State:
    pass


def _spec_and_sharding(cell, devices):
    """The spec, and the sharding of the inputs: records on their last
    axis over a 1-D mesh of the chips in `jax.make_mesh` order, the order
    the sort's own mesh takes (or the spec's mesh, where it has one)."""
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec

    from repro.sort import SortSpec

    spec = SortSpec(**cell.config["spec"])
    mesh = spec.mesh or jax.make_mesh(
        (len(devices),), (spec.axis_name,), devices=devices,
        axis_types=(AxisType.Auto,))
    return spec, NamedSharding(mesh, PartitionSpec(None, spec.axis_name))


def setup(cell, seed: int, devices, seconds: float) -> State:
    import jax

    import repro.sort as rs

    if not hasattr(rs, "sort_records"):
        raise SystemExit("perfbench: this program has no "
                         "repro.sort.sort_records; nothing measured")
    cfg, mix = cell.config, cell.traffic
    st = State()
    st.rs = rs
    st.spec, sharding = _spec_and_sharding(cell, devices)
    st.chips = len(devices)
    st.n = int(cfg["records_per_chip"]) * st.chips
    st.inputs = [traffic.device_keys(seed, j, st.n, mix, sharding=sharding)
                 for j in range(int(mix["inputs"]))]
    for j in range(int(mix["warmup_calls"])):
        out = rs.sort_records(st.inputs[j % len(st.inputs)], st.spec)
        jax.block_until_ready((out.shards, out.counts))
        out.counters()   # warms the counters' host reads too
        del out
    st.check_at = traffic.checked_calls(seed, mix)
    return st


def measure(st: State, seconds: float, tracer) -> dict:
    import jax

    calls, overflow, counted, kept, errors = [], [], [], {}, []
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
                    out = st.rs.sort_records(x, st.spec)
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
            counted.append(out.counters() if ok else None)   # after t_done
            if ok and i in st.check_at:
                kept[i] = (out.shards, out.counts)
            i += 1
            if t_done - t0 >= seconds:
                break
    last = len(calls) - 1
    st.check_at = sorted({min(j, last) for j in st.check_at})
    if last in st.check_at and out is not None:
        kept[last] = (out.shards, out.counts)
    del out
    st.kept = kept
    ovf = [None if o is None else int(np.max(np.asarray(o))) for o in overflow]
    counted = [c for c in counted if c is not None]
    rounds = [c["rounds_used"] for c in counted]
    wire = [sum(c["wire_bytes"]) for c in counted]
    sent = [sum(c["sent_bytes"]) for c in counted]
    failed = sum(1 for c, o in zip(calls, ovf) if not c[4] or o)
    return {"calls": calls, "overflow": ovf, "attempted": len(calls),
            "failed": failed, "records_per_chip": st.n // st.chips,
            "rounds": rounds, "wire_bytes": wire, "sent_bytes": sent,
            "notes": [f"calls {len(calls)}, failed {failed}, "
                      f"compared calls {st.check_at}, splitter rounds "
                      f"{rounds}, wire bytes a call {wire[:1]}, record "
                      f"bytes among them {sent[:1]}"] + errors[:5]}


def collect(st: State) -> dict:
    """Host copies of the compared calls' outputs and inputs, as (n, 100)
    uint8 records; then the device state is released."""
    unpack = record_reference.records_from_words
    n_inputs = len(st.inputs)
    compared = []
    for i in st.check_at:
        got = st.kept.pop(i, None)
        if got is None:
            compared.append((i, None))
            continue
        shards, counts = (np.asarray(a) for a in got)
        words = np.concatenate(
            [shards[s, :, :counts[s]] for s in range(shards.shape[0])],
            axis=1)
        compared.append((i, unpack(words)))
    inputs = {i % n_inputs: unpack(np.asarray(st.inputs[i % n_inputs]))
              for i in st.check_at}
    st.inputs = st.kept = None
    return {"compared": compared, "inputs": inputs, "n_inputs": n_inputs}


def outputs(collected: dict) -> list:
    """(input records, output records or None) of each compared call."""
    return [(collected["inputs"][i % collected["n_inputs"]], got)
            for i, got in collected["compared"]]


def check(collected: dict, produce=None) -> reference.Comparison:
    """Compare each compared call with the reference sort of its input:
    `mismatched_records` counts the positions whose 100 bytes differ once
    each run of equal keys in the output is ordered by whole record.
    `produce`, when given, stands in for the program (a control)."""
    cmp = reference.Comparison()
    mismatched, unanswered = 0, 0
    want_of = {}
    for records, got in outputs(collected):
        if produce is not None:
            got = produce(records)
        if got is None:
            unanswered += 1
            continue
        k = id(records)
        if k not in want_of:
            want_of[k] = record_reference.reference_sort(records)
        mismatched += record_reference.mismatched_records(got, want_of[k])
    cmp.add("mismatched_records", mismatched, 0)
    cmp.add("unanswered", unanswered, 0)
    return cmp
