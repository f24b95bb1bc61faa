"""Chip smoke test: the sort engine's main path on a TPU, end to end.

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # four chips: sharded HSS only

One chip (every phase through the public front doors, `repro.sort` and
`repro.serve`, with seeded random keys):

  sort      `sort()` of 2^28 int32 keys (1 GiB) against `np.sort`;
            `argsort` and `sort_kv` of 2^24 keys against NumPy.
  kernels   `kernel_policy="pallas"` against `"xla"`, bit for bit, at 2^22
            keys: the bitonic local sort (one row and a batch), the
            histogram kernel (`dispatch.probe_ranks`) and the merge cascade
            (`dispatch.merge_runs`, `dispatch.merge_ragged`). The kernels
            must be compiled Mosaic (`tpu_custom_call`), not interpret mode.
  grouping  `semisort`, `groupby_aggregate` and `top_k` of 2^24 Zipf keys
            against NumPy.
  serve     a `ServiceRunner` answers 16 mixed-kind requests of 2^20 keys,
            each bit-identical to the direct call; health stays "ok" and
            the degraded, breaker and fallback counters stay 0.

Four chips (`--chips 4`): `sort(algorithm="hss")` of 2^26 int32 keys per
chip, once per exchange (dense, ragged, allgather) and once with
`verify="cheap"`, each against `np.sort`, with overflow 0 and the achieved
imbalance within 1 + eps.

Each phase prints its wall time (compilation included) and result on a
line of its own. The last line is the JSON object
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
A failed check exits non-zero without that line, and so does a machine
without a TPU: there is no CPU fallback.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime.compile_cache import use_compile_cache  # noqa: E402

INT32_HI = np.iinfo(np.int32).max   # the hi sentinel; keys stay below it


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def report(phase: str, name: str, t0: float, detail: str) -> None:
    kind = jax.devices()[0].device_kind
    print(f"[{kind}] {phase:8s} {name:28s} {time.perf_counter() - t0:9.3f}s"
          f"  {detail}", flush=True)


def int32_keys(rng, n: int, lo: int = -(2 ** 31), hi: int = INT32_HI):
    return rng.integers(lo, hi, size=n, dtype=np.int32)


def phase_sort(rng, n_sort: int, n_perm: int) -> None:
    from repro.sort import argsort, sort, sort_kv

    x = int32_keys(rng, n_sort)
    t0 = time.perf_counter()
    got = sort(jnp.asarray(x)).gather()
    want = np.sort(x)
    check(np.array_equal(got, want), "sort != np.sort")
    report("sort", f"sort n={n_sort}", t0, "== np.sort")

    # tagged (index-packed) keys of 32 + 24 bits need int64 packing
    with jax.enable_x64(True):
        k = int32_keys(rng, n_perm)
        v = rng.standard_normal(n_perm).astype(np.float32)
        want_order = np.argsort(k, kind="stable")
        t0 = time.perf_counter()
        order = argsort(jnp.asarray(k))
        check(np.array_equal(order, want_order),
              "argsort != np.argsort(kind='stable')")
        report("sort", f"argsort n={n_perm}", t0, "== np.argsort (stable)")
        t0 = time.perf_counter()
        sk, sv = sort_kv(jnp.asarray(k), v)
        check(np.array_equal(sk, k[want_order])
              and np.array_equal(sv, v[want_order]), "sort_kv != NumPy")
        report("sort", f"sort_kv n={n_perm}", t0, "== NumPy stable pairs")


def phase_kernels(rng, n: int) -> None:
    from repro.kernels import dispatch, interpret_default
    from repro.sort import SortSpec, sort

    check(not interpret_default(), "Pallas kernels would run interpreted")
    x = int32_keys(rng, n)
    xd = jnp.asarray(x)
    hlo = jax.jit(lambda v: dispatch.local_sort(v, policy="pallas")).lower(
        xd).compile().as_text()
    check("tpu_custom_call" in hlo, "no Mosaic kernel in the local sort")

    def same(name, fn, detail="pallas == xla, bit for bit", want=None):
        t0 = time.perf_counter()
        a = np.asarray(fn("pallas"))
        b = np.asarray(fn("xla"))
        check(a.dtype == b.dtype and np.array_equal(a, b),
              f"{name}: pallas != xla")
        if want is not None:
            check(np.array_equal(a, want), f"{name}: != NumPy")
        report("kernels", name, t0, detail)

    same(f"sort n={n}", lambda pol: sort(
        xd, SortSpec(kernel_policy=pol)).gather(), want=np.sort(x))
    rows = xd.reshape(4, n // 4)
    same(f"local_sort_batched 4x{n // 4}",
         lambda pol: dispatch.local_sort_batched(rows, policy=pol))
    probes = jnp.asarray(np.sort(int32_keys(rng, 255)))
    same(f"probe_ranks {n}x255",
         lambda pol: dispatch.probe_ranks(xd, probes, policy=pol))
    runs = jnp.sort(rows, axis=1)
    same(f"merge_runs 4x{n // 4}",
         lambda pol: dispatch.merge_runs(runs, policy=pol))
    # ragged runs at traced offsets, sentinel-filled slack
    counts = np.array([n // 8, n // 4 - 1, 1, n // 8 + 3], np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    buf = np.full(n, INT32_HI, np.int32)
    for s, c in zip(starts, counts):
        buf[s:s + c] = np.sort(int32_keys(rng, c))
    same(f"merge_ragged {n}", lambda pol: dispatch.merge_ragged(
        jnp.asarray(buf), jnp.asarray(starts), jnp.asarray(counts),
        policy=pol, slot=n // 4), want=np.sort(buf))


def phase_grouping(rng, n: int, k: int = 1000) -> None:
    from repro.data.distributions import make_adversarial
    from repro.sort import groupby_aggregate, semisort, top_k

    z = make_adversarial("ZIPF_HH", n, seed=int(rng.integers(2 ** 31)))
    zd = jnp.asarray(z)
    uniq, counts = np.unique(z, return_counts=True)

    t0 = time.perf_counter()
    g = semisort(zd).gather()
    runs = 1 + int(np.count_nonzero(g[1:] != g[:-1]))
    check(np.array_equal(np.sort(g), np.sort(z)), "semisort lost keys")
    check(runs == uniq.size, "semisort left a key group split")
    report("grouping", f"semisort n={n}", t0,
           f"{uniq.size} groups, each contiguous")

    t0 = time.perf_counter()
    gk, gc = groupby_aggregate(zd, op="count")
    check(np.array_equal(gk, uniq) and np.array_equal(gc, counts),
          "groupby count != np.unique")
    report("grouping", f"groupby count n={n}", t0, "== np.unique counts")

    v = rng.integers(-1000, 1000, size=n, dtype=np.int32)
    order = np.argsort(z, kind="stable")
    starts = np.unique(z[order], return_index=True)[1]
    want_sum = np.add.reduceat(v[order].astype(np.int64), starts)
    with jax.enable_x64(True):
        t0 = time.perf_counter()
        sk, sums = groupby_aggregate(zd, v, op="sum")
    check(np.array_equal(sk, uniq) and np.array_equal(sums, want_sum),
          "groupby sum != NumPy")
    report("grouping", f"groupby sum n={n}", t0, "== NumPy reduceat")

    t0 = time.perf_counter()
    top = np.asarray(top_k(zd, k))
    check(np.array_equal(top, np.sort(z)[::-1][:k]), "top_k != NumPy")
    report("grouping", f"top_k k={k} n={n}", t0, "== np.sort tail")


def phase_serve(rng, n: int, n_requests: int = 16) -> None:
    from repro.serve import ServiceConfig, ServiceRunner
    from repro.sort import argsort, semisort, sort, sort_kv, top_k

    kinds = ("sort", "argsort", "sort_kv", "semisort", "top_k")
    reqs = []
    for i in range(n_requests):
        kind = kinds[i % len(kinds)]
        # index tags of 20 bits leave 10 for an int32-packed key range
        hi = 1024 if kind in ("argsort", "sort_kv") else INT32_HI
        x = int32_keys(rng, n, lo=0 if hi == 1024 else -(2 ** 31), hi=hi)
        v = (rng.integers(0, 2 ** 31, size=n, dtype=np.int32)
             if kind == "sort_kv" else None)
        reqs.append((kind, x, v, 100 if kind == "top_k" else None))

    def direct(kind, x, v, k):
        xd = jnp.asarray(x)
        if kind == "sort":
            return sort(xd).gather()
        if kind == "argsort":
            return argsort(xd)
        if kind == "sort_kv":
            return sort_kv(xd, v)
        if kind == "semisort":
            return semisort(xd).gather()
        return np.asarray(top_k(xd, k))

    config = ServiceConfig(max_batch=8, max_delay_ms=200.0)
    t0 = time.perf_counter()
    with ServiceRunner(config=config) as runner:
        with concurrent.futures.ThreadPoolExecutor(n_requests) as pool:
            futs = [pool.submit(runner.submit, x, kind=kind, values=v,
                                param=k) for kind, x, v, k in reqs]
            served = [f.result() for f in futs]
        metrics, health = runner.metrics(), runner.health()
    report("serve", f"{n_requests} requests n={n}", t0,
           f"{metrics['batches']} batches, health={health['health']}")

    t0 = time.perf_counter()
    for (kind, x, v, k), got in zip(reqs, served):
        want = direct(kind, x, v, k)
        if kind == "sort_kv":
            same = all(np.array_equal(a, b) for a, b in zip(got, want))
        else:
            same = np.array_equal(got, want)
        check(same, f"served {kind} != direct call")
        if kind == "sort":
            check(np.array_equal(got, np.sort(x)), "served sort != np.sort")
    report("serve", "served == direct calls", t0,
           f"{n_requests} results bit-identical")
    check(health["health"] == "ok", f"health {health['health']!r}")
    counters = {k: metrics[k] for k in (
        "errors", "degraded_requests", "degraded_errors", "batch_retries",
        "bisections", "executor_restarts", "verify_failures",
        "verify_fallbacks")}
    for name, br in health["breakers"].items():
        counters[f"breaker {name} trips"] = br["trips"]
        counters[f"breaker {name} failures"] = br["failures"]
    check(not any(counters.values()), f"nonzero counters {counters}")
    check(metrics["served"] == n_requests, "not every request was served")


def phase_hss(rng, n_per_chip: int, p: int, eps: float = 0.05,
              exchanges=("dense", "ragged", "allgather")) -> None:
    from repro.sort import SortSpec, sort

    x = int32_keys(rng, n_per_chip * p)
    want = np.sort(x)
    xd = jnp.asarray(x)
    runs = [(ex, "off") for ex in exchanges] + [("dense", "cheap")]
    for exchange, verify in runs:
        spec = SortSpec(algorithm="hss", eps=eps, exchange=exchange,
                        verify=verify)
        t0 = time.perf_counter()
        out = sort(xd, spec)
        got = out.gather()
        check(np.array_equal(got, want), f"hss {exchange}/{verify} != np.sort")
        overflow = int(np.max(np.asarray(out.overflow)))
        counts = np.asarray(out.counts)
        imbalance = float(counts.max()) * p / x.size
        check(overflow == 0, f"hss {exchange}/{verify}: overflow {overflow}")
        check(imbalance <= 1 + eps,
              f"hss {exchange}/{verify}: imbalance {imbalance}")
        report("hss", f"{exchange} verify={verify} p={p}", t0,
               f"== np.sort, overflow=0, imbalance={imbalance:.6f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); refusing to "
              "run on anything else", file=sys.stderr)
        return 2
    check(len(devices) == args.chips,
          f"expected {args.chips} chip(s), jax found {len(devices)}")

    print(f"compile cache: {use_compile_cache()}", flush=True)
    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phase_hss(rng, n_per_chip=1 << 26, p=4)
    else:
        phase_sort(rng, n_sort=1 << 28, n_perm=1 << 24)
        phase_kernels(rng, n=1 << 22)
        phase_grouping(rng, n=1 << 24)
        phase_serve(rng, n=1 << 20)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
