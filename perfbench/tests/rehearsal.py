"""A miniature checkout for CPU rehearsals of the harness.

`make_root(dir)` copies the harness, systems and metrics beside a
BENCHMARK.json of two tiny cells that run on host devices in seconds:
`t.hss`, the direct-sort system over 4 devices, and `t.serve`, the sort
service on one. `run_cell` runs one through `run.main` and returns the
parsed result line."""
from __future__ import annotations

import contextlib
import io
import json
import os
import pathlib
import shutil
import sys

# the same host device count the repository's own tests ask for, set
# before jax starts: `t.hss` sorts over 4 of them
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

PERFBENCH = pathlib.Path(__file__).resolve().parents[1]
if str(PERFBENCH) not in sys.path:
    sys.path.insert(0, str(PERFBENCH))
SRC = PERFBENCH.parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

CONFIGS = {
    "t_hss": {"system": "sort_direct", "chips": 4, "keys_per_chip": 8192,
              "key_dtype": "int32", "spec": {"algorithm": "hss", "eps": 0.05}},
    "t_serve": {"system": "sort_service", "chips": 1, "key_dtype": "int32",
                "spec": {}, "service": {"max_batch": 4, "max_delay_ms": 5.0,
                                        "max_queue_depth": 256,
                                        "pad_batches": True}},
}
CHIPS = {"t.hss": 4, "t.serve": 1}
TRAFFIC = {
    "t_closed": {"loop": "closed", "distribution": "uniform", "inputs": 2,
                 "warmup_calls": 1, "check_calls": 2, "check_among_first": 3},
    "t_open": {"loop": "open", "arrivals": "poisson", "rate_per_s": 60,
               "kind": "sort", "distribution": "uniform",
               "sizes": [1024, 16384], "weights": [2, 1], "pool_keys": 65536,
               "check_requests": 1000},
}


def make_root(root: pathlib.Path) -> pathlib.Path:
    root = pathlib.Path(root)
    for part in ("harness", "systems", "metrics"):
        shutil.copytree(PERFBENCH / part, root / "perfbench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "perfbench" / "configs").mkdir()
    (root / "perfbench" / "traffic").mkdir()
    for name, cfg in CONFIGS.items():
        (root / "perfbench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, mix in TRAFFIC.items():
        (root / "perfbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    real = json.loads((PERFBENCH.parent / "BENCHMARK.json").read_text())
    bench = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "test", "reduced": [], "why": "test",
                     "file": f"perfbench/configs/{n}.json"} for n in CONFIGS],
        "workloads": [
            {"name": "t.hss", "config": "t_hss", "traffic": "t_closed",
             "chips": 4, "why": "test"},
            {"name": "t.serve", "config": "t_serve", "traffic": "t_open",
             "chips": 1, "why": "test"}],
        # the real metrics, moved onto the test cells
        "end_to_end": [_retarget(m) for m in real["end_to_end"]],
        "per_layer": [_retarget(m) for m in real["per_layer"]],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _retarget(metric: dict) -> dict:
    m = dict(metric)
    if "workloads" in m:
        m["workloads"] = ["t.hss" if w.startswith("hss_sort.") else "t.serve"
                          for w in m["workloads"]]
    return m


def _mesh(workload: str):
    """A 1-D mesh over the cell's own host devices, where the spec would
    otherwise take all of them."""
    import jax
    from jax.sharding import AxisType
    chips = CHIPS[workload]
    return jax.make_mesh((chips,), ("sort",), devices=jax.devices()[:chips],
                         axis_types=(AxisType.Auto,))


def run_cell(root, workload: str, *, seed: int = 3, trace: int = 0,
             produce=None) -> dict:
    """Run one cell in this process; returns the parsed result line (or
    raises with what the run printed)."""
    import run as bench_run
    rehearsal = {"peaks": PEAKS, "spec": {"mesh": _mesh(workload)}}
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(
            ["--workload", workload, "--seed", str(seed), "--seconds", "1",
             "--trace", str(trace)], root=pathlib.Path(root),
            rehearsal=rehearsal,
            produce=produce)
    lines = out.getvalue().strip().splitlines()
    if rc != 0 or not lines:
        raise RuntimeError(f"rc={rc}\n{out.getvalue()}\n{err.getvalue()}")
    result = json.loads(lines[-1])
    result["_stdout"], result["_stderr"] = out.getvalue(), err.getvalue()
    return result
