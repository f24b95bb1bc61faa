"""The reduction from a trace to metrics, on hand-worked traces: one
made up, and an excerpt recorded from a TPU v5e run of a cell."""
import json
import pathlib

import pytest

import rehearsal  # noqa: F401  (puts the harness on sys.path)
from harness.trace import Trace, op_family

DATA = pathlib.Path(__file__).resolve().parent / "data"

MADE_UP = {
    "window": [0, 100],
    "devices": {"TPU:0": [
        ["sort.1", "jit_per_shard", 10, 20],
        ["all-to-all.2", "jit_per_shard", 25, 10],   # overlaps sort.1
        ["fusion.3", "jit_sort", 50, 10],
        ["sort.4", "jit_sort", 95, 10],              # runs past the window
    ]},
    "host": [
        ["perfbench.window", 0, 100],
        ["perfbench.front_door", 0, 50],
        ["PjitFunction(sort)", 60, 30],
    ],
}


def test_busy_time_is_the_union_of_op_intervals_in_the_window():
    t = Trace.from_dict(MADE_UP)
    assert t.busy_intervals("TPU:0") == [[10, 35], [50, 60], [95, 100]]
    assert t.busy_s("TPU:0") == pytest.approx(40e-9)
    assert t.idle_share("TPU:0") == pytest.approx(0.6)
    assert t.window_s == pytest.approx(100e-9)


def test_ops_match_by_name_and_module():
    t = Trace.from_dict(MADE_UP)
    assert t.op_seconds("TPU:0", [r"^sort"]) == pytest.approx(25e-9)
    assert t.op_seconds("TPU:0", [r"^sort"], "per_shard") == pytest.approx(
        20e-9)
    assert t.op_seconds("TPU:0", [r"^all-to-all", r"^fusion"]) == \
        pytest.approx(20e-9)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    t = Trace.from_dict(MADE_UP)
    assert t.idle_gaps("TPU:0") == [(0, 10), (35, 50), (60, 95)]
    assert t.host_label(60, 95) == "PjitFunction(sort)"
    assert t.host_label(35, 50) == "perfbench.front_door"
    b = t.breakdown()
    assert b["device_ops"][0] == ["sort", pytest.approx(25e-9)]
    assert {name for name, _ in b["device_ops"]} == {"sort", "all-to-all",
                                                     "fusion"}
    assert b["idle_gaps"][0] == ["PjitFunction(sort)", pytest.approx(35e-9)]


def test_op_family_drops_numeric_suffixes():
    assert op_family("all-to-all.12") == "all-to-all"
    assert op_family("fusion.3.1") == "fusion"
    assert op_family("_network_kernel") == "_network_kernel"


def test_plain_form_round_trips():
    t = Trace.from_dict(MADE_UP)
    assert Trace.from_dict(json.loads(json.dumps(t.as_dict()))).as_dict() \
        == t.as_dict()


def _recorded():
    return json.loads((DATA / "tpu_serve_excerpt.json").read_text())


def test_recorded_tpu_excerpt_busy_time_and_local_sort():
    """One batch of `serve.small_uniform` on a TPU v5 lite: 26 ops, none
    overlapping, so busy time is their summed duration; the bitonic local
    sort is the four ops of its own module (793 + 124687 + 7127 + 541 ns)."""
    d = _recorded()
    t = Trace.from_dict(d)
    ops = d["devices"]["TPU:0"]
    assert len(ops) == 26
    assert t.busy_s("TPU:0") == pytest.approx(sum(o[3] for o in ops) / 1e9)
    assert t.idle_share("TPU:0") == pytest.approx(
        1 - t.busy_s("TPU:0") / t.window_s)
    assert t.op_seconds("TPU:0", [""], r"^jit_local_sort_batched$") == \
        pytest.approx(133148e-9)
    assert t.op_seconds("TPU:0", [r"^reduce_"]) == pytest.approx(1242e-9)


def test_recorded_tpu_excerpt_breakdown():
    t = Trace.from_dict(_recorded())
    b = t.breakdown()
    assert b["device_ops"][0] == ["local_sort_batched",
                                  pytest.approx(131814e-9)]
    assert len(b["idle_gaps"]) == 10
    longest = max(e - s for s, e in t.idle_gaps("TPU:0"))
    assert b["idle_gaps"][0][1] == pytest.approx(longest / 1e9)
    hosts = {h[0] for h in _recorded()["host"]}
    assert all(label in hosts for label, _ in b["idle_gaps"])
