"""BENCHMARK.json, its cells, and the files the harness finds by name.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own, found by the name `BENCHMARK.json` gives it:

    configs      the file each `configs` entry names
    traffic      perfbench/traffic/<traffic>.json
    systems      perfbench/systems/<config "system">.py
    metrics      perfbench/metrics/<metric name>.py, one `read(run)` each

So a later cell, configuration, traffic mix or metric is added by adding
files and entries; no file here needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any

PERFBENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = PERFBENCH.parent


def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file by path; its name may hold dots (`queue_wait_ms.serve`)."""
    name = "perfbench_" + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no benchmark file {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with its files loaded."""
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # metric entries this cell reports with --trace 1


def load_cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "perfbench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def load_system(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "perfbench" / "systems" / f"{name}.py")


def load_reader(metric: str, root: pathlib.Path = ROOT):
    return load_module(root / "perfbench" / "metrics" / f"{metric}.py")


def load_peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The published peaks of one device kind. A kind the table lacks is an
    error, never a default."""
    table = load_json(root / "perfbench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"perfbench/peaks.json ({sorted(table['devices'])})")
    return dict(table["devices"][device_kind])


@dataclasses.dataclass
class Run:
    """What one run measured, handed to every metric reader.

    data holds the system's own readings (per call or per request, host
    clock), counters read from the program, and the notes it prints;
    trace is a `harness.trace.Trace` of the window, or None without
    `--trace 1`."""
    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    window_s: float
    peaks: dict
    data: dict
    trace: Any = None
