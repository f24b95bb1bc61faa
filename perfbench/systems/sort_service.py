"""`repro.serve.SortService` under an open loop of sort requests.

The configuration gives the service's settings and the spec; the traffic
mix gives the arrival rate, the request sizes and their weights. Requests
are host NumPy slices of one key pool made on the device from the seed,
and results come back as NumPy, as the HTTP front end sees them.

Set-up warms every shape the mix uses: each request size at every batch
size the service can launch (powers of two up to `max_batch`, which its
padding produces). The window then submits each request when it is due,
from one asyncio task per request on the service's own event loop, and
times it from when it was due until its result is back. After the last
arrival the loop waits for every request (a minute at most); one that
fails, is refused or never returns counts with an infinite latency.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from harness import reference, traffic

GRACE_S = 60.0
REFUSED = "refused"   # a typed admission refusal: failed, but not wrong


class State:
    pass


def _batch_sizes(max_batch: int) -> list:
    sizes, b = [], 1
    while b < max_batch:
        sizes.append(b)
        b *= 2
    return sizes + [max_batch]


def setup(cell, seed: int, devices, seconds: float) -> State:
    from repro.serve import ServiceConfig, SortService
    from repro.sort import SortSpec

    cfg, mix = cell.config, cell.traffic
    if mix.get("kind", "sort") != "sort":
        raise ValueError(f"this system serves kind 'sort' only, got "
                         f"{mix['kind']!r}")
    st = State()
    st.config = ServiceConfig(**cfg["service"])
    st.spec = SortSpec(**cfg["spec"])
    st.pool = np.asarray(traffic.device_keys(seed, 0, int(mix["pool_keys"]),
                                             mix))
    st.sched = traffic.open_schedule(seed, mix, seconds)
    st.loop = asyncio.new_event_loop()
    st.svc = SortService(spec=st.spec, config=st.config)

    async def warm():
        for n in sorted(set(int(s) for s in mix["sizes"])):
            for b in _batch_sizes(st.config.max_batch):
                await asyncio.gather(*[st.svc.submit(st.pool[i:i + n])
                                       for i in range(b)])

    st.loop.run_until_complete(warm())
    st.svc.metrics.reset()
    return st


def measure(st: State, seconds: float, tracer) -> dict:
    import jax

    from repro.serve.errors import Overloaded

    sched = st.sched
    n_req = len(sched["size"])
    sent = np.full(n_req, np.nan)
    done = np.full(n_req, np.inf)
    results, errors, refused = {}, [], []

    async def one(i, n, off):
        try:
            res = await st.svc.submit(st.pool[off:off + n])
        except Overloaded as e:
            refused.append(f"request {i}: {e}")
            results[i] = REFUSED
            return
        except Exception as e:   # failed: counted, not fatal
            errors.append(f"request {i}: {type(e).__name__}: {e}")
            return
        done[i] = time.perf_counter()
        if sched["check"][i]:
            results[i] = res

    async def drive():
        tasks = []
        with jax.profiler.TraceAnnotation("perfbench.arrivals"):
            for i in range(n_req):
                delay = t0 + sched["due_s"][i] - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                sent[i] = time.perf_counter()
                tasks.append(asyncio.ensure_future(
                    one(i, int(sched["size"][i]), int(sched["offset"][i]))))
        _, pending = await asyncio.wait(tasks, timeout=GRACE_S)
        for t in pending:
            t.cancel()
        if pending:
            await asyncio.wait(pending)

    with tracer:
        t0 = time.perf_counter()
        st.loop.run_until_complete(drive())
    due = t0 + sched["due_s"]
    latency_ms = np.where(np.isfinite(done), (done - due) * 1e3, np.inf)
    late_ms = (sent - due) * 1e3
    snap = st.svc.metrics.snapshot()
    waits = [(b["queue_wait_ms"]["mean"], b["requests"])
             for b in snap["buckets"].values()]
    served = sum(r for _, r in waits)
    failed = int(np.count_nonzero(~np.isfinite(done)))
    st.results = results
    return {
        "latency_ms": latency_ms.tolist(),
        "sizes": sched["size"].tolist(),
        "completed": np.isfinite(done).tolist(),
        "attempted": n_req, "failed": failed,
        "queue_wait_ms": (sum(m * r for m, r in waits) / served
                          if served else None),
        "batches": snap["batches"],
        "notes": [
            f"requests {n_req}, failed {failed} (refused {len(refused)}), "
            f"batches {snap['batches']}, "
            f"mean occupancy {served / max(snap['batches'], 1):.3f}",
            f"generator lateness ms: p50 {np.median(late_ms):.3f} "
            f"p95 {np.percentile(late_ms, 95):.3f} max {late_ms.max():.3f}",
        ] + errors[:5],
    }


def collect(st: State) -> dict:
    """The compared requests' keys and answers; then the service closes."""
    sched = st.sched
    compared = []
    for i in np.flatnonzero(sched["check"]):
        n, off = int(sched["size"][i]), int(sched["offset"][i])
        compared.append((st.pool[off:off + n], st.results.get(int(i))))
    try:
        st.loop.run_until_complete(
            asyncio.wait_for(st.svc.aclose(), GRACE_S))
    finally:
        st.loop.close()
    st.svc = st.results = None
    return {"compared": compared}


def outputs(collected: dict) -> list:
    return collected["compared"]


def check(collected: dict, produce=None) -> reference.Comparison:
    """Compare each compared answer with `np.sort` of its request.
    `produce`, when given, stands in for the program (the control)."""
    cmp = reference.Comparison()
    mismatched, unanswered = 0, 0
    for keys, got in outputs(collected):
        if got is REFUSED:
            continue   # refused at admission: failed and infinitely late
        if produce is not None:
            got = produce(keys)
        if got is None:
            unanswered += 1
            continue
        mismatched += reference.mismatched_keys(
            got, reference.reference_sort(keys))
    cmp.add("mismatched_keys", mismatched, 0)
    cmp.add("unanswered", unanswered, 0)
    return cmp
