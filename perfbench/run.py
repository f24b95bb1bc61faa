"""Run one benchmark cell on the chip and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are read from
`BENCHMARK.json` at the root of the checkout. The run sets the system up
(keys made on the device from the seed, every shape the cell uses warmed
from the compile cache in `<checkout>/.jax_cache`), measures for
`--seconds`, reads the device's peak memory, releases the program's
state, and compares what the timed path produced with the plain
reference. With `--trace 1` the window runs under the profiler and the
cell's per-layer metrics are reported instead of its end-to-end ones.

Standard output ends with one JSON line: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` a `breakdown`, and last
`checks`, each compared number with its limit; standard error ends with
the same numbers. Without a TPU, or with another number of chips than
the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from harness import bench  # noqa: E402
from harness.trace import Tracer  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def say(*lines, err=False):
    for line in lines:
        print(line, file=sys.stderr if err else sys.stdout, flush=True)


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv=None, *, root: pathlib.Path = bench.ROOT, rehearsal=None,
         produce=None) -> int:
    """Run a cell. `rehearsal` (tests only) is a dict with the `peaks` to
    use and `spec` fields to add to the configuration's spec (a mesh over
    the cell's own host devices): the run then accepts the CPU and leaves
    the persistent cache alone. `produce` (the control) stands in for the
    program's answers in the comparison."""
    args = parse(argv)
    cell = bench.load_cell(args.workload, root)
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal is None and platform != "tpu":
        say(f"perfbench: no TPU (jax found {platform}); nothing measured",
            err=True)
        return 2
    if rehearsal is None and len(devices) != cell.chips:
        say(f"perfbench: {cell.name} needs {cell.chips} chip(s), jax found "
            f"{len(devices)}", err=True)
        return 2
    if len(devices) < cell.chips:
        say(f"perfbench: {cell.name} needs {cell.chips} devices", err=True)
        return 2
    devices = devices[:cell.chips]
    kind = devices[0].device_kind
    if rehearsal is None:
        peaks = bench.load_peaks(kind, root)
        from repro.runtime.compile_cache import use_compile_cache
        use_compile_cache()
        # small programs too: every later run must load, not compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    else:
        peaks = rehearsal["peaks"]
        cell.config["spec"].update(rehearsal.get("spec", {}))

    system = bench.load_system(cell.config["system"], root)
    state = system.setup(cell, args.seed, devices, args.seconds)
    setup_s = time.perf_counter() - T_START

    tracer = Tracer(enabled=args.trace == 1)
    data = system.measure(state, args.seconds, tracer)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(devices)}
    collected = system.collect(state)
    del state
    gc.collect()
    comparison = system.check(collected, produce)
    del collected

    trace = None
    if args.trace:
        trace = tracer.load(len(devices))
        busy = [trace.busy_s(d) for d in trace.devices]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = trace.window_s
        say("device idle share per chip: " + ", ".join(
            f"{d} {100 * trace.idle_share(d):.3f}%" for d in trace.devices))
    run = bench.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                    setup_s=setup_s, window_s=tracer.window_s, peaks=peaks,
                    data=data, trace=trace)

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = bench.load_reader(m["name"], root).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": comparison.correct, "attempted": data["attempted"],
              "failed": data["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = trace.breakdown()
    result["checks"] = comparison.as_dict()

    say(f"setup_s {setup_s:.3f}, window_s {tracer.window_s:.3f}",
        *data["notes"])
    say(*comparison.lines(), err=True)
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
