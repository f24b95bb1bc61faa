"""Find a serving cell's knee: its open loop at several fixed rates.

    python3 perfbench/sweep.py --workload serve.small_uniform --seed 5 \
        --seconds 6 --rates 50 100 200 400

One process sets the cell up once, then runs its traffic mix at each rate
in turn for `--seconds`, and prints one JSON line per rate: the median
and 95th-percentile latency, the failures, and how the latency of the
last fifth of the arrivals compares with the first fifth (a ratio well
above 1 is a queue that grows through the window). The knee is the
highest rate whose queue does not grow; a cell then fixes its rate in its
traffic file. Runs on the chip only, like `run.py`.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from harness import bench, traffic  # noqa: E402
from harness.stats import percentile  # noqa: E402
from harness.trace import Tracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell.chips:
        print(f"sweep: needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    system = bench.load_system(cell.config["system"])
    state = system.setup(cell, args.seed, devices, args.seconds)
    for rate in args.rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        state.sched = traffic.open_schedule(args.seed, mix, args.seconds)
        state.svc.metrics.reset()
        data = system.measure(state, args.seconds, Tracer(enabled=False))
        lat = data["latency_ms"]
        fifth = max(1, len(lat) // 5)
        first, last = lat[:fifth], lat[-fifth:]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "failed": data["failed"],
            "p50_ms": percentile(lat, 0.5), "p95_ms": percentile(lat, 0.95),
            "growth": percentile(last, 0.5) / percentile(first, 0.5),
            "queue_wait_ms": data["queue_wait_ms"],
            "batches": data["batches"], "notes": data["notes"]}),
            flush=True)
    system.collect(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
