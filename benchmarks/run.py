"""Benchmark orchestrator — one bench per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--only fig4]

Prints ``name,us_per_call,derived`` CSV rows (None time => analytic bench).

A parallel-sorting paper's benches need shards: ask XLA for 8 host devices
(NOT the dry-run's 512 — that stays in launch/dryrun.py's own process, and
`benchmarks.bench_sort_collectives` runs alone for the same reason).
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import pathlib
import sys
import time
import traceback

from benchmarks.common import emit

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

BENCHES = [
    ("table2", "benchmarks.bench_table2_complexity"),
    ("fig2", "benchmarks.bench_fig2_sample_size"),
    ("table4", "benchmarks.bench_table4_rounds"),
    ("gamma", "benchmarks.bench_gamma_decay"),
    ("fig4", "benchmarks.bench_fig4_weak_scaling"),
    ("fig5", "benchmarks.bench_fig5_distributions"),
    ("fig6", "benchmarks.bench_fig6_histogramming"),
    ("fig3", "benchmarks.bench_fig3_duplicates"),
    ("fig7", "benchmarks.bench_fig7_application"),
    ("kernels", "benchmarks.bench_kernels"),
    ("sort", "benchmarks.bench_sort"),
    ("serve", "benchmarks.bench_serve"),
    ("moe", "benchmarks.bench_moe_dispatch"),
    ("roofline", "benchmarks.roofline"),
]


def _write_json(fname: str, bench: str, rows) -> None:
    """Machine-readable bench snapshot at the repo root (the perf-trajectory
    artifact: committed per change, uploaded by CI)."""
    import jax
    payload = {
        "bench": bench,
        "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "rows": [{"name": name, "us_per_call": us, "derived": derived}
                 for name, us, derived in rows],
    }
    (REPO_ROOT / fname).write_text(json.dumps(payload, indent=2) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for key, module in BENCHES:
        if args.only and args.only != key:
            continue
        t0 = time.time()
        try:
            import importlib
            mod = importlib.import_module(module)
            rows = mod.run()
            emit(rows)
            if key == "kernels":
                _write_json("BENCH_kernels.json", key, rows)
            if key == "sort":
                _write_json("BENCH_sort.json", key, rows)
            if key == "serve":
                _write_json("BENCH_serve.json", key, rows)
            print(f"# {key}: {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {key}: FAILED", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
