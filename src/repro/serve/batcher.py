"""The dynamic batcher: shape buckets + batch-size-or-deadline flushing.

This module is pure queueing policy — no jax, no dispatch. `SortService`
owns one `DynamicBatcher` per event loop and hands it admitted requests;
the batcher groups them by `repro.sort.bucket_key` (length, dtype, kind,
spec fingerprint — the same derivation the compiled-executable cache
keys on, so one bucket == one executable family) and fires a flush
callback when a bucket either

  * reaches `max_batch` requests ("size" — the throughput-optimal flush), or
  * has waited `max_delay_s` since its first pending request ("deadline"
    — the latency bound for a trickle of traffic), or
  * the service drains it explicitly ("drain" / shutdown).

This is the dynamic-batching pattern LLM inference servers use to turn a
per-request engine into a high-traffic one; here the engine underneath is
`repro.sort.sort_batched`, whose cost per batch is one launch and a
B-independent set of collectives — which is exactly why occupancy is
worth chasing (DESIGN.md Section 6).
"""
from __future__ import annotations

import asyncio
import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Request:
    """One admitted sort request, queued until its bucket flushes.

    deadline is absolute `loop.time()` (None = no deadline); expired
    requests are dropped from the batch at dispatch, resolved with
    DeadlineExceeded, and never poison the surviving requests.

    The life cycle is stamped on the same clock as `t_submit`
    (`loop.time()` is `time.monotonic()`): `t_flush` when its bucket
    flushes, `t_start`/`t_end` when the dispatch thread first starts and
    last ends a run that holds it (retries and bisection included).
    """
    kind: str                  # "sort" | "argsort" | "sort_kv" |
    #                            "semisort" | "top_k"
    x: Any                     # 1-D key array (host or device)
    values: Any                # sort_kv payload, else None
    spec: Any                  # SortSpec (argsort/sort_kv: already stable)
    key: tuple                 # repro.sort.bucket_key(...)
    future: asyncio.Future
    t_submit: float            # loop.time() at admission
    deadline: float | None = None
    param: Any = None          # kind-specific scalar (top_k: the k)
    t_flush: float | None = None
    t_start: float | None = None
    t_end: float | None = None


class DynamicBatcher:
    """Per-bucket pending queues with size-or-deadline flushing.

    Single-threaded: every method must run on the owning event loop (the
    service guarantees this). `flush_cb(key, requests, reason)` is called
    synchronously from the loop; the service wraps it in a task.
    """

    def __init__(self, *, max_batch: int, max_delay_s: float,
                 flush_cb: Callable[[tuple, list, str], None]):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = max_batch
        self.max_delay_s = max_delay_s
        self.flush_cb = flush_cb
        self._pending: dict[tuple, list[Request]] = {}
        self._timers: dict[tuple, asyncio.TimerHandle] = {}

    @property
    def depth(self) -> int:
        """Requests waiting in buckets (not yet handed to a flush)."""
        return sum(len(v) for v in self._pending.values())

    def add(self, req: Request) -> None:
        pend = self._pending.setdefault(req.key, [])
        pend.append(req)
        if len(pend) >= self.max_batch:
            self._fire(req.key, "size")
        elif len(pend) == 1:
            loop = asyncio.get_running_loop()
            self._timers[req.key] = loop.call_later(
                self.max_delay_s, self._fire, req.key, "deadline")

    def _fire(self, key: tuple, reason: str) -> None:
        timer = self._timers.pop(key, None)
        if timer is not None:
            timer.cancel()
        reqs = self._pending.pop(key, None)
        if reqs:
            now = asyncio.get_running_loop().time()
            for r in reqs:
                r.t_flush = now
            self.flush_cb(key, reqs, reason)

    def flush_all(self, reason: str = "drain") -> None:
        for key in list(self._pending):
            self._fire(key, reason)
