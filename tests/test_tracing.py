"""What the program records for a profiler and for its counters
(repro.runtime.spans): scopes on the ops of the sharded launch, the
front door's and the service's host spans, a request's life cycle in
the serving metrics, and the compile counter."""
import asyncio
import glob
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.parallel.compat import make_mesh
from repro.runtime import compile_cache, spans
from repro.serve import ServiceConfig, SortService
from repro.serve.metrics import PHASES
from repro.sort import SortSpec, driver, sort
from repro.sort.adapters import detect_duplicates, key_range

ROUND_ALGORITHMS = ("hss", "multistage")


@pytest.mark.parametrize("algorithm", ["hss", "sample_random",
                                       "sample_regular", "ams",
                                       "multistage"])
def test_launch_carries_every_scope(monkeypatch, algorithm):
    """The audited `jit_per_shard` program of each algorithm, lowered on
    four host devices, names each scope of the table on its ops."""
    built = []
    real = driver._jit_donated

    def keep(fn):
        built.append(real(fn))
        return built[-1]

    monkeypatch.setattr(driver, "_jit_donated", keep)
    devices = jax.devices()[:4]
    axes = (("outer", "inner"), (2, 2)) if algorithm == "multistage" else (
        ("sort",), (4,))
    mesh = make_mesh(axes[1], axes[0], devices=devices)
    spec = SortSpec(algorithm=algorithm, mesh=mesh, tag=False,
                    verify="cheap", exchange="allgather")
    x = jnp.asarray(np.random.default_rng(1).permutation(4096)
                    .astype(np.int32))
    out = sort(x, spec)
    assert np.array_equal(out.gather(), np.arange(4096))
    (fn,) = built
    lowered = fn.lower(x.reshape(axes[1] + (-1,)), jax.random.key(0))
    assert "@jit_per_shard" in lowered.as_text()
    # the compiled ops' `op_name` metadata: what a TPU trace event carries
    op_names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    want = set(spans.SCOPES) - (set() if algorithm in ROUND_ALGORITHMS
                                else {spans.SPLITTER_ROUND})
    for name in want:
        assert any(f"/{name}/" in o for o in op_names), name


@pytest.mark.parametrize("keys, want", [([3, 1, 2, 9], False),
                                        ([3, 1, 3, 9], True),
                                        ([[1, 2], [5, 5]], True),
                                        ([[1, 2], [2, 1]], False)])
def test_detect_duplicates_is_one_program(keys, want):
    x = jnp.asarray(np.asarray(keys, np.int32))
    assert bool(detect_duplicates(x)) is want
    assert f"@{spans.DETECT_MODULE}" in detect_duplicates.lower(x).as_text()


@pytest.mark.parametrize("keys, dtype", [([3, -7, 2, 9], np.int32),
                                         ([[1, 2], [5, -5]], np.int32),
                                         ([0, 2**32 - 1, 7], np.uint32)])
def test_key_range_is_one_program(keys, dtype):
    """The plan reads min and max together: one program, one array."""
    x = jnp.asarray(np.asarray(keys, dtype))
    got = np.asarray(key_range(x))
    assert got.shape == (2,) and got.dtype == dtype
    assert [int(v) for v in got] == [int(np.min(keys)), int(np.max(keys))]
    assert f"@{spans.RANGE_MODULE}" in key_range.lower(x).as_text()


def test_compile_counter_counts_backend_compiles():
    before = compile_cache.compile_count()
    f = jax.jit(lambda v: v * 3 + 11)
    f(jnp.arange(7)).block_until_ready()
    once = compile_cache.compile_count()
    assert once >= before + 1
    f(jnp.arange(7)).block_until_ready()      # in-memory executable: none
    assert compile_cache.compile_count() == once
    assert driver.exec_cache.stats()["compiles"] >= once


def _one_chip_spec():
    return SortSpec(tag=False, mesh=make_mesh((1,), ("sort",),
                                              devices=jax.devices()[:1]))


SIZES = (256, 512, 1024)


def _serve(requests, config):
    """Serve `requests` (arrays) one after another; returns the metrics
    snapshot and the answers."""
    async def go():
        async with SortService(spec=_one_chip_spec(), config=config) as svc:
            answers = [await svc.submit(x) for x in requests]
            return svc.metrics.snapshot(), answers
    return asyncio.run(go())


def test_lifecycle_phases_add_up_to_the_latency():
    """Each bucket holds one request, so its phase sums are that request's
    life cycle: they add up to its latency within a millisecond."""
    rng = np.random.default_rng(5)
    reqs = [rng.permutation(4 * n)[:n].astype(np.int32) for n in SIZES]
    snap, answers = _serve(reqs, ServiceConfig(max_batch=4, max_delay_ms=2))
    for x, got in zip(reqs, answers):
        assert np.array_equal(got, np.sort(x))
    assert len(snap["buckets"]) == len(SIZES)
    for b in snap["buckets"].values():
        life = b["lifecycle_ms"]
        assert life["requests"] == b["requests"] == 1
        total = sum(life[ph]["sum"] for ph in PHASES)
        assert total == pytest.approx(b["latency_ms"]["mean"], abs=1.0)
        assert all(life[ph]["sum"] >= 0 for ph in PHASES)
        assert life["batch_host"]["sum"] > 0
        # the deadline flush: a lone request waits out max_delay
        assert life["batcher_wait"]["sum"] >= 1.0


def _events_by_thread(path):
    from jax.profiler import ProfileData
    by_thread = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                by_thread.setdefault((plane.name, line.name), []).extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.duration_ns > 0)
    return by_thread


def test_serve_batch_span_encloses_front_door_and_results(tmp_path):
    rng = np.random.default_rng(6)
    reqs = [rng.permutation(4 * 512)[:512].astype(np.int32)
            for _ in range(3)]
    config = ServiceConfig(max_batch=4, max_delay_ms=2)
    _serve(reqs[:1], config)                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("test.window"):
            _serve(reqs, config)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = _events_by_thread(path)
    window = next(e for evs in events.values() for e in evs
                  if e[0] == "test.window")
    checked = 0
    for evs in events.values():
        for name, s, e in evs:
            if name != spans.BATCH:
                continue
            assert window[1] <= s and e <= window[2]
            inner = {n for n, s2, e2 in evs if s <= s2 and e2 <= e}
            assert {spans.FRONT_DOOR, spans.RESULTS, spans.STACK,
                    spans.H2D, spans.LAUNCH, spans.GATHER} <= inner, inner
            checked += 1
    assert checked == len(reqs)


def test_detect_span_nests_in_plan_only_where_it_detects(tmp_path):
    """`sort.detect` is a host span inside `sort.plan`, and a plan records
    it only where a tag fits: narrow-range keys detect, full-range int32
    keys (32 key bits, x64 off) never do."""
    assert spans.DETECT in spans.HOST_SPANS
    rng = np.random.default_rng(7)
    inputs = {
        "narrow": rng.integers(0, 64, size=1024, dtype=np.int32),
        "full": rng.integers(np.iinfo(np.int32).min, np.iinfo(np.int32).max,
                             size=1024, dtype=np.int32),
    }
    spec = SortSpec(mesh=_one_chip_spec().mesh)   # tag=None: auto
    for x in inputs.values():                # compile outside the trace
        sort(jnp.asarray(x), spec)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name, x in inputs.items():
            with spans.span(f"test.{name}"):
                np.testing.assert_array_equal(
                    sort(jnp.asarray(x), spec).gather(), np.sort(x))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    detects = {}
    for evs in _events_by_thread(path).values():
        windows = {n: (s, e) for n, s, e in evs if n.startswith("test.")}
        plans = [(s, e) for n, s, e in evs if n == spans.PLAN]
        for n, s, e in evs:
            if n != spans.DETECT:
                continue
            assert any(ps <= s and e <= pe for ps, pe in plans)
            for w, (ws, we) in windows.items():
                if ws <= s and e <= we:
                    detects[w] = detects.get(w, 0) + 1
    assert detects == {"test.narrow": 1}
