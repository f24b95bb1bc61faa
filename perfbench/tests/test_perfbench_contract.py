"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
resolves to the file that serves it."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_exactly_their_keys(section):
    for entry in BENCH[section]:
        extra = set(entry) - KEYS[section]
        if section in ("end_to_end", "per_layer"):
            extra -= {"workloads"}
        assert KEYS[section] <= set(entry) and not extra, entry["name"]
        assert NAME.match(entry["name"]), entry["name"]


def test_names_units_and_text_fields():
    names = [e["name"] for s in KEYS for e in BENCH[s]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in BENCH["configs"] + BENCH["workloads"] + BENCH["per_layer"]:
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
                assert "\t" not in e[key]


def test_end_to_end_metrics_and_bounds():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert set(e2e) == {"setup_s", "sort_keys_per_s", "serve_p50_ms",
                        "serve_p95_ms"}
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "workloads" not in e2e["setup_s"]


def test_every_cell_reports_what_it_must():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    assert len(pairs) == len(cells)
    for name, w in cells.items():
        assert w["chips"] in (1, 4)
        e2e = {m["name"] for m in BENCH["end_to_end"]
               if name in m.get("workloads", [name])}
        assert "setup_s" in e2e and len(e2e) >= 2, name
        layer = [m for m in BENCH["per_layer"]
                 if name in m.get("workloads", [name])]
        assert layer, name
        for m in layer:
            assert m["moves"] in e2e, (name, m["name"])


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"


def test_names_resolve_to_files():
    perfbench = ROOT / "perfbench"
    for c in BENCH["configs"]:
        assert c["file"].startswith("perfbench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (perfbench / "systems" / f"{cfg['system']}.py").is_file()
        for key in c["reduced"]:
            assert key in cfg, (c["name"], key)
        for key in ("source", "reduced", "assumed", "why", "guarantee"):
            assert key in cfg, (c["name"], key)
    for w in BENCH["workloads"]:
        mix_file = perfbench / "traffic" / f"{w['traffic']}.json"
        mix = json.loads(mix_file.read_text())
        keys = perfbench / "traffic" / "keys" / f"{mix['distribution']}.py"
        assert keys.is_file(), (w["name"], keys)
        if mix["loop"] == "open":
            arrivals = f"{mix['arrivals']}.py"
            arrivals = perfbench / "traffic" / "arrivals" / arrivals
            assert arrivals.is_file(), (w["name"], arrivals)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (perfbench / "metrics" / f"{m['name']}.py").is_file()
