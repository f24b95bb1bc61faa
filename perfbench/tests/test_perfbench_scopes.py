"""Readings of what the program names in a trace (`harness.scopes` and the
readers of program spans, scopes and kernels), on hand-worked traces."""
import pytest

import rehearsal  # noqa: F401  (puts the harness on sys.path)
from harness import bench, scopes
from harness.trace import Trace

# one chip: a launch whose splitter loop (a `while` and its body, both
# scoped) precedes the exchange; the detection program before it
SCOPED = {
    "window": [0, 1000],
    "devices": {"TPU:0": [
        ["sort.1", "jit_detect_duplicates", 0, 100, "jit(detect)/sort"],
        ["sort.2", "jit_per_shard", 100, 50,
         "jit(per_shard)/shard_map/local_sort/sort"],
        ["while.3", "jit_per_shard", 150, 200,
         "jit(per_shard)/shard_map/splitters/splitter_round/while"],
        ["probe_ranks_histogram.4", "jit_per_shard", 160, 20,
         "jit(per_shard)/shard_map/splitters/splitter_round/while/body/"
         "probe_ranks_histogram/pallas_call"],
        ["fusion.5", "jit_per_shard", 400, 300,
         "jit(per_shard)/shard_map/exchange/gather"],
        ["all-to-all.6", "jit_per_shard", 650, 100,       # overlaps fusion.5
         "jit(per_shard)/shard_map/exchange/all_to_all"],
        ["merge_sorted_runs_network.7", "jit_per_shard", 900, 200,
         "jit(per_shard)/shard_map/merge/merge_sorted_runs_network"],
    ]},
    "host": [
        ["perfbench.window", 0, 1000],
        ["sort.front_door", 0, 400],
        ["sort.plan", 50, 340],
        ["ReadSyncFlag", 360, 30],
        ["sort.launch", 392, 6],
    ],
}


def test_scope_seconds_count_a_loop_and_its_body_once():
    assert scopes.scope_seconds(SCOPED, "TPU:0", "/splitters/") == \
        pytest.approx(200e-9)
    # fusion.5 and the all-to-all overlap by 50 ns: 300 + 100 - 50
    assert scopes.scope_seconds(SCOPED, "TPU:0", "/exchange/") == \
        pytest.approx(350e-9)
    # merge_sorted_runs_network.7 runs 100 ns past the window
    assert scopes.scope_seconds(SCOPED, "TPU:0", "/merge/") == \
        pytest.approx(100e-9)
    assert scopes.scope_seconds(SCOPED, "TPU:0", "", "^jit_detect") == \
        pytest.approx(100e-9)
    assert scopes.scope_seconds(SCOPED, "TPU:0", "/audit/") == 0


def test_top_scope_is_the_first_table_name_on_the_path():
    names = ("local_sort", "splitters", "splitter_round", "exchange")
    assert scopes.top_scope(SCOPED["devices"]["TPU:0"][3][4], names) == \
        "splitters"
    assert scopes.top_scope("jit(detect)/sort", names) == ""


@pytest.mark.parametrize("gap, label, want", [
    ((355, 358), "x", "sort.plan > x"),         # the innermost holder
    ((20, 45), "x", "sort.front_door > x"),
    ((340, 420), "y", "y"),                      # no program span holds it
    ((500, 600), "no host span", "no host span"),
])
def test_program_label_names_the_innermost_program_span(gap, label, want):
    t = scopes.plain(SCOPED)
    names = {"sort.front_door", "sort.plan", "sort.launch"}
    assert scopes.program_label(t.host, *gap, label, names) == want


def test_program_label_extends_the_harness_label():
    t = scopes.plain(SCOPED)
    base = t.host_label(360, 390)
    assert base == "ReadSyncFlag"
    assert scopes.program_label(t.host, 360, 390, base,
                                {"sort.plan", "sort.front_door"}) == \
        "sort.plan > ReadSyncFlag"


def test_plain_form_drops_only_the_scope():
    t = scopes.plain(SCOPED)
    assert isinstance(t, Trace)
    assert all(len(op) == 4 for op in t.devices["TPU:0"])
    assert t.busy_s("TPU:0") == pytest.approx(800e-9)


def _run(trace, **data):
    cell = bench.load_cell("hss_sort.uniform_p4")
    return bench.Run(cell=cell, seed=1, seconds=1.0, setup_s=1.0,
                     window_s=trace.window_s, peaks={}, data=data,
                     trace=trace)


def _read(metric, run):
    return bench.load_reader(metric).read(run)


def test_sort_readers_on_the_made_up_trace():
    t = scopes.plain(SCOPED)
    run = _run(t, calls=[(8, 0, 0, 1, True), (8, 0, 0, 1, True)])
    assert _read("detect_ms.sort", run) == pytest.approx(100e-6 / 2)
    assert _read("splitter_rounds.sort", run) == pytest.approx(0.5)


SERVE_HOST = [
    ["perfbench.window", 100, 1000],
    ["runtime.compile", 50, 5],          # before the window: not counted
    ["serve.batch", 200, 300],
    ["serve.results", 400, 80],
    ["runtime.compile", 300, 5],
    ["serve.batch", 600, 100],
    ["serve.results", 650, 40],
    ["serve.batch", 1050, 100],          # runs past the window
]


def test_serve_and_compile_readers_on_made_up_spans():
    t = Trace({"TPU:0": []}, SERVE_HOST, [100, 1100])
    run = _run(t)
    assert _read("batch_host_ms.serve", run) == pytest.approx(200e-6)
    assert _read("results_ms.serve", run) == pytest.approx(60e-6)
    assert _read("compiles.serve", run) == 1
    assert _read("compiles.sort", run) == 1
    quiet = _run(Trace({"TPU:0": []}, SERVE_HOST[:1], [100, 1100]))
    assert _read("compiles.serve", quiet) == 0
    assert _read("batch_host_ms.serve", quiet) is None


@pytest.mark.parametrize("metric", ["splitter_rounds.sort", "compiles.sort",
                                    "batch_host_ms.serve", "results_ms.serve",
                                    "compiles.serve"])
def test_readers_of_program_names_read_nothing_without_the_table(
        monkeypatch, metric):
    """A program from before the table (as the parent of this benchmark's
    readers is) gives no reading, and no error."""
    monkeypatch.setattr(scopes, "table", lambda: None)
    t = Trace(SCOPED["devices"], SERVE_HOST, [100, 1100])
    assert _read(metric, _run(t, calls=[(8, 0, 0, 1, True)])) is None


def test_detection_reads_nothing_where_the_program_ran_it_eagerly():
    t = Trace({"TPU:0": [["sort.1", "jit_sort", 0, 100]]}, [], [0, 1000])
    assert _read("detect_ms.sort", _run(t, calls=[(8, 0, 0, 1, True)])) \
        is None


def test_op_scope_reads_tf_op_then_the_hlo_text():
    assert scopes.op_scope({"tf_op": "a/exchange/b"}) == "a/exchange/b"
    hlo = ('%fusion.5 = s32[4]{0} fusion(...), metadata={op_type="gather" '
           'op_name="jit(per_shard)/exchange/gather"}')
    assert scopes.op_scope({"long_name": hlo}) == "jit(per_shard)/exchange/gather"
    assert scopes.op_scope({}) == ""


def test_load_scoped_reads_a_cpu_profile(tmp_path):
    """A CPU profile of one front-door call: the host spans come back, and
    the XLA CPU client's ops stand in for a device, with no scope."""
    import glob
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness.trace import WINDOW_SPAN
    from repro.parallel.compat import make_mesh
    from repro.sort import SortSpec, sort

    spec = SortSpec(mesh=make_mesh((1,), ("sort",),
                                   devices=jax.devices()[:1]))
    x = jnp.asarray(np.random.default_rng(2).permutation(512)
                    .astype(np.int32))
    sort(x, spec).gather()                      # compiled before the window
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        sort(x, spec).gather()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    scoped = scopes.load_scoped(path)
    names = {h[0] for h in scoped["host"]}
    assert {"sort.front_door", "sort.plan", "sort.launch",
            "sort.gather"} <= names
    ops = scoped["devices"]["CPU:0"]
    assert ops and all(len(op) == 5 for op in ops)
    assert any(op[1] == "jit_detect_duplicates" for op in ops)
    t = scopes.plain(scoped)
    lo, hi = t.window
    for s, d in scopes.spans_in_window(t, "sort.front_door"):
        assert lo <= s and s + d <= hi


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):   # one entry of a protobuf map<int64, message>
    return _field(1, key) + _field(2, message)


def test_device_scopes_read_tf_op_from_the_event_metadata(tmp_path):
    """A hand-built XSpace as a TPU writes it: the op's `tf_op` is a stat
    of its event metadata, keyed by program id and HLO text; the text
    alone answers only where every program agrees on it."""
    stat_names = {1: "tf_op", 2: "program_id"}
    metas = [
        (7, "%fusion.5 = s32[4] fusion(x)", "jit(per_shard)/exchange/gather",
         5),
        (8, "%copy.1 = s32[4] copy(y)", "", 5),
        (9, "%iota = s32[4] iota()", "jit(a)/local_sort/iota", 5),
        (10, "%iota = s32[4] iota()", "jit(b)/merge/iota", 6),
    ]
    plane = _field(2, "/device:TPU:0")
    for mid, name in stat_names.items():
        plane += _field(5, _entry(mid, _field(1, mid) + _field(2, name)))
    for mid, text, tf_op, pid in metas:
        stats = _field(5, _field(1, 2) + _field(3, pid))
        if tf_op:
            stats += _field(5, _field(1, 1) + _field(5, tf_op))
        plane += _field(4, _entry(mid, _field(1, mid) + _field(2, text)
                                  + stats))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, _field(2, "/host:CPU")) + _field(1, plane))
    table = scopes.device_scopes(str(path))["TPU:0"]
    assert table[(5, metas[0][1])] == "jit(per_shard)/exchange/gather"
    assert table[metas[0][1]] == "jit(per_shard)/exchange/gather"
    assert (5, metas[1][1]) not in table      # an XLA copy has no scope
    assert table[(5, metas[2][1])] == "jit(a)/local_sort/iota"
    assert table[(6, metas[3][1])] == "jit(b)/merge/iota"
    assert table[metas[2][1]] == ""           # two programs disagree


def _recorded_sort():
    import json
    import pathlib
    data = pathlib.Path(__file__).resolve().parent / "data"
    return json.loads((data / "tpu_sort_scoped_excerpt.json").read_text())


def test_recorded_sort_call_by_scope():
    """One 2^29-key call of `hss_sort.uniform_p4` on chip 0 of a TPU v5
    lite 2x2 host, by scope: no two ops of a scope overlap, so each
    reading is the plain sum of its ops' durations (loops left out)."""
    d = _recorded_sort()
    assert len(d["devices"]["TPU:0"]) == 512
    want_ns = {"exchange": 8597138680, "merge": 1672288361,
               "splitters": 872707823, "local_sort": 409376271}
    for name, ns in want_ns.items():
        assert scopes.scope_seconds(d, "TPU:0", f"/{name}/",
                                    "^jit_per_shard$") == \
            pytest.approx(ns / 1e9), name
    detect_ns = 2058646392
    busy = scopes.plain(d).busy_s("TPU:0")
    assert busy == pytest.approx(13.634583846)
    # the scopes plus the detection program: 99.8% of the chip's busy time
    assert (sum(want_ns.values()) + detect_ns) / 1e9 / busy > 0.95


def test_recorded_sort_call_readers_and_names():
    d = _recorded_sort()
    t = scopes.plain(d)
    run = _run(t, calls=[(2 ** 29, 0, 0, 1, True)])
    # the detection's all-gather began 44,465 ns before the window: the
    # reader counts only its part inside (2,058,646,392 - 44,465 ns)
    assert _read("detect_ms.sort", run) == pytest.approx(2058.601927)
    assert _read("splitter_rounds.sort", run) == 2
    # every op the merge reader matches by name is the merge's, by scope
    merge_ops = [op for op in d["devices"]["TPU:0"]
                 if op[0].startswith("merge_sorted_runs")]
    assert {op[0].rsplit(".", 1)[0] for op in merge_ops} == {
        "merge_sorted_runs_strided", "merge_sorted_runs_network"}
    assert all("/merge/" in op[4] for op in merge_ops)
    a2a = [op for op in d["devices"]["TPU:0"]
           if op[0].startswith("all_to_all")]
    assert len(a2a) == 2 and all("/exchange/" in op[4] for op in a2a)


def test_recorded_sort_call_gaps_by_program_span():
    """The idle gaps over a millisecond: three while the front door plans
    (detection's answer and the key range are read back) and one while
    it launches, each labelled `ReadSyncFlag` by the harness alone; the
    longest, after the launch's last op, lies where the front door has
    returned and the benchmark blocks on the result."""
    d = _recorded_sort()
    t = scopes.plain(d)
    names = {h[0] for h in d["host"]} - {"ReadSyncFlag", "perfbench.window",
                                        "perfbench.front_door",
                                        "perfbench.block"}
    labels = [scopes.program_label(t.host, s, e, t.host_label(s, e), names)
              for s, e in t.idle_gaps("TPU:0") if e - s > 1e6]
    assert labels == 3 * ["sort.plan > ReadSyncFlag"] + [
        "sort.launch > ReadSyncFlag", "ReadSyncFlag", "perfbench.block",
        "perfbench.block"]
