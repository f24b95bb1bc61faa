"""The shared host-level sort driver (DESIGN.md Section 3.2).

Every distributed sort in the repo — HSS, the sample-sort baselines, AMS,
and multi-stage HSS — shares one skeleton: reshape the global key array onto
a mesh, run a shard_map-resident `sort_fn(local, rng) -> 6-tuple`, and
reassemble the per-shard results. This module is that skeleton, promoted out
of the old private `repro.core.hss._driver` and generalized:

  * mesh resolution: accepts an explicit Mesh (1-D or N-D) or builds one
    over all devices from `(axis_name, size)` pairs;
  * p == 1 short-circuit: a plain local `jnp.sort`, no collectives;
  * non-divisible inputs: instead of the old `ValueError`, inputs whose
    length does not divide the shard count are sentinel-padded up to the
    next multiple. Pads are the globally largest keys, so they land on the
    tail of the last shard; any that the exchange counted as valid are
    stripped back out of the returned counts (`strip_sentinel_counts`);
  * shard_map construction via the version-compat wrapper in
    repro.parallel.compat.

The shard-level contract: `sort_fn(local, rng)` returns
`(out, n_valid, splitter_keys, splitter_ranks, overflow, stats)` where `out`
is the shard's sentinel-padded sorted slice of static shape and `stats` is a
`SplitterStats` (or any fixed pytree, replicated across shards).

`run_batched` launches B equal-length requests in one shard_map launch
(DESIGN.md Section 6): it takes the same single-request `sort_fn` and maps
it over this shard's (B, n_local) block with one `jax.vmap`. Both entry
points take a `cache_key` that opts into the compiled-executable cache
(`exec_cache`) so steady-state serving never re-traces.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.common import hi_sentinel, lo_sentinel
from repro.core.splitters import BATCH_AXES
from repro.parallel.compat import make_mesh, shard_map
from repro.runtime import compile_cache, spans


class MeshPlan(NamedTuple):
    mesh: object          # jax.sharding.Mesh
    axis_names: tuple     # mesh axes the sort spans, outermost first
    sizes: tuple          # per-axis sizes; p == prod(sizes)
    p: int


class ExecutableCache:
    """Compiled-executable cache for the sort drivers (DESIGN.md Sec. 6.3).

    `run`/`run_batched` rebuild their shard_map'd callable per invocation, so
    without this cache jax re-traces and re-compiles every call — a fresh
    trace per serving request. The cache stores the *jitted callable* keyed
    by everything that determines the traced program: shape bucket, dtype,
    the SortSpec fingerprint, and the mesh fingerprint (the front-door
    derives the key; see repro.sort.api). A hit reuses the callable object,
    which makes the second call with the same shape bucket go straight to
    jax's compiled-executable fast path — zero retracing (`traces` counts
    actual trace-time executions of the shard body, so tests can pin this).

    Input buffers are donated on backends that support donation (not CPU),
    so steady-state serving re-uses the request buffer for the shard-padded
    input instead of allocating per call.

    The caller owns key correctness: a key must capture every closure the
    sort_fn bakes into the program. Callers with unhashable/opaque state
    (custom local_sort_fn, warm-start probes) pass cache_key=None and keep
    today's per-call behavior.

    Eviction is LRU with a capacity cap (`max_entries`): a hit refreshes
    the entry, an insert past capacity evicts the least-recently-used
    executable and bumps `evictions`. The counters are exposed through
    `stats()` — the serving metrics registry (repro.serve.metrics)
    snapshots them, and the dynamic batcher attributes per-batch deltas to
    its shape buckets. All bookkeeping is lock-protected: the serving
    dispatch thread and the main thread share the global instance.
    """

    def __init__(self, max_entries: int = 64):
        self.max_entries = max_entries
        self._fns: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.traces = 0     # trace-time executions of driver shard bodies

    def get_or_build(self, key, build):
        if key is None:
            return build()
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self.hits += 1
                self._fns.move_to_end(key)
                return fn
            self.misses += 1
        fn = build()   # outside the lock: builds may nest cache lookups
        with self._lock:
            cur = self._fns.get(key)
            if cur is not None:     # racer built it first: keep theirs
                return cur
            self._fns[key] = fn
            while len(self._fns) > self.max_entries:
                self._fns.popitem(last=False)
                self.evictions += 1
        return fn

    def contains(self, key) -> bool:
        """Whether `key` holds a warm executable (no LRU refresh)."""
        with self._lock:
            return key in self._fns

    def stats(self) -> dict:
        """Counter snapshot for metrics consumers (plain dict, safe to
        diff: the serving layer attributes per-batch deltas to buckets).
        `compiles` is the process's backend compiles so far
        (`repro.runtime.compile_cache.compile_count`), eager ops' too: it
        moves on a warm path where `misses` cannot, and `clear()` leaves
        it alone."""
        with self._lock:
            total = self.hits + self.misses
            return {"size": len(self._fns), "max_entries": self.max_entries,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions, "traces": self.traces,
                    "hit_rate": self.hits / total if total else 0.0,
                    "compiles": compile_cache.compile_count()}

    def clear(self):
        with self._lock:
            self._fns.clear()
            self.hits = self.misses = self.evictions = self.traces = 0

    def __len__(self):
        return len(self._fns)


exec_cache = ExecutableCache()


def _jit_donated(fn):
    """jit with the key-array input donated where the backend supports it
    (donation is a no-op warning on CPU, so gate it off there)."""
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(fn, donate_argnums=donate)


def resolve_mesh(mesh, axis_names, sizes=None) -> MeshPlan:
    """Build/validate the mesh the sort runs over.

    mesh=None: make a fresh mesh over all devices; `sizes` (if given) must
    multiply to the device count, else all devices go on one axis.
    mesh given: its named axes must cover `axis_names`.
    """
    axis_names = tuple(axis_names)
    if mesh is not None:
        missing = [a for a in axis_names if a not in mesh.shape]
        extra = [a for a in mesh.shape if a not in axis_names]
        if missing or extra:
            raise ValueError(
                f"sort over axes {axis_names} needs a mesh with exactly "
                f"those axes; got {dict(mesh.shape)}")
        sizes = tuple(mesh.shape[a] for a in axis_names)
        return MeshPlan(mesh, axis_names, sizes, int(np.prod(sizes)))
    devices = jax.devices()
    p = len(devices)
    if sizes is None:
        if len(axis_names) != 1:
            raise ValueError("sizes required for a multi-axis auto mesh")
        sizes = (p,)
    if int(np.prod(sizes)) != p:
        raise ValueError(f"mesh sizes {sizes} != {p} devices")
    mesh = make_mesh(tuple(sizes), axis_names, devices=devices)
    return MeshPlan(mesh, axis_names, tuple(sizes), p)


def factor_stages(p: int) -> tuple[int, int]:
    """(r1, r2) with r1*r2 == p and r1 the largest divisor <= sqrt(p)."""
    r1 = 1
    for d in range(1, int(np.sqrt(p)) + 1):
        if p % d == 0:
            r1 = d
    return r1, p // r1


def pad_to_shards(x: jax.Array, p: int):
    """Sentinel-pad x up to a multiple of p. Returns (padded, n_pad).

    Sentinel-valued real keys are permitted: `run` counts them device-side
    *before* padding and restores them into the post-sort counts
    (`strip_sentinel_counts(..., n_restore=...)`), so they are served as
    data while the pads are stripped. The old implementation instead raised
    here after a `bool(jnp.max(x) == pad_value)` check — a host-blocking
    device round-trip inside every non-divisible dispatch.
    """
    n = x.shape[0]
    n_pad = (-n) % p
    if n_pad == 0:
        return x, 0
    pad = jnp.full((n_pad,), hi_sentinel(x.dtype), x.dtype)
    return jnp.concatenate([x, pad]), n_pad


def pad_to_shards_lo(x: jax.Array, p: int):
    """LO-sentinel counterpart of `pad_to_shards` for max-seeking paths
    (repro.sort.semisort.top_k): pads must never displace real keys from
    the top of the order, so they enter as the globally *smallest* value.
    A pad colliding with a real dtype-min key is harmless for values-only
    top-k — the outputs are identical by value."""
    n = x.shape[0]
    n_pad = (-n) % p
    if n_pad == 0:
        return x, 0
    pad = jnp.full((n_pad,), lo_sentinel(x.dtype), x.dtype)
    return jnp.concatenate([pad, x]), n_pad


def strip_sentinel_counts(shards, counts, n_pad=0, n_restore=None):
    """Exclude sentinel-valued entries from per-shard valid counts.

    Used when the driver sentinel-padded a non-divisible input: pads travel
    through the exchange as ordinary (globally largest) keys and some
    strategies count them as valid. Counting the sentinels actually present
    in each valid prefix — rather than assuming `n_pad` survived — stays
    exact even when the exchange dropped keys.

    When the input also contained genuine sentinel-valued keys (`n_restore`,
    a traced count the caller took before padding), they are
    indistinguishable from the pads by value, so the stripped tail is
    partially restored: only the sentinels present *beyond* `n_pad` are
    provably data, so exactly that many are kept. If the exchange dropped
    sentinel entries, the loss is therefore charged against the restored
    data keys first — conservative by design: a pad can never surface as
    data, at the price of under-restoring under drops (which the overflow
    counter already reports). Restored slots go to the earliest shards
    whose prefixes held sentinels — sentinels only occupy the global tail,
    so this keeps the gathered output sorted. All device-side; no host
    sync.
    """
    cap = shards.shape[1]
    counts = jnp.asarray(counts, jnp.int32)
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]
    pads = valid & (shards == hi_sentinel(shards.dtype))
    stripped = jnp.sum(pads, axis=1).astype(jnp.int32)
    counts = counts - stripped
    if n_restore is None:
        return counts
    keep = jnp.clip(jnp.sum(stripped) - n_pad, 0,
                    jnp.asarray(n_restore, jnp.int32))
    before = jnp.cumsum(stripped) - stripped
    return counts + jnp.clip(keep - before, 0, stripped)


def run(sort_fn, x, *, mesh=None, axis_names=("sort",), sizes=None, seed=0,
        n_real=None, local_sort_fn=None, cache_key=None):
    """Run a shard-level sort over a mesh; returns the raw 6-tuple with
    leading (p, ...) shard dims: (shards, counts, keys, ranks, overflow,
    stats). `x` is a 1-D key array, or records as (words, n) planes
    sharded over the mesh on their last axis: each shard gets its
    (words, n_local) block as placed, unpadded, and returns (words,
    out_cap). Inputs the driver itself had to sentinel-pad get their counts
    corrected via `strip_sentinel_counts`; callers that pre-padded with
    non-sentinel values (the tagged adapter path) correct counts on decode.
    `n_real` (default: len(x)) is the non-pad key count for the p==1 path,
    and `local_sort_fn` (default jnp.sort) is what that path runs — callers
    with a kernel_policy pass a dispatch-routed sort so a single-device
    mesh still honors the policy. `cache_key` (hashable) opts into the
    compiled-executable cache: it must capture everything `sort_fn` bakes
    into the trace (see ExecutableCache).
    """
    plan = resolve_mesh(mesh, axis_names, sizes)
    p = plan.p
    n_real = x.shape[-1] if n_real is None else n_real
    if p == 1:
        with spans.span(spans.LAUNCH):
            out = (local_sort_fn or jnp.sort)(x)
        return (out[None], jnp.full((1,), n_real, jnp.int32),
                jnp.zeros((0,), x.dtype), jnp.zeros((0,), jnp.int32),
                jnp.zeros((), jnp.int32), None)
    n_sent_real = None
    if x.ndim == 2:   # record planes (words, n), sharded on their last axis
        if x.shape[1] % p:
            raise ValueError(f"{x.shape[1]} records do not split into {p} "
                             "equal shards")
        xs, n_pad, in_spec = x, 0, P(None, plan.axis_names)
    else:
        if (-x.shape[0]) % p:   # count sentinel-valued data keys pre-pad
            n_sent_real = jnp.sum(
                (x == hi_sentinel(x.dtype)).astype(jnp.int32))
        x, n_pad = pad_to_shards(x, p)
        xs = x.reshape(plan.sizes + (x.shape[0] // p,))
        in_spec = P(*plan.axis_names)
    naxes = len(plan.axis_names)

    def build():
        def per_shard(block, key):
            exec_cache.traces += 1
            local = block if x.ndim == 2 else block.reshape(-1)
            me = jnp.int32(0)
            for name, size in zip(plan.axis_names, plan.sizes):
                me = me * size + jax.lax.axis_index(name)
            rng = jr.fold_in(key, me)
            out, n_valid, keys, ranks, ovf, stats = sort_fn(local, rng)
            lead = (1,) * naxes
            return (out.reshape(lead + out.shape),
                    jnp.asarray(n_valid, jnp.int32).reshape(lead),
                    keys, ranks, ovf, stats)

        sharded = P(*plan.axis_names)
        # record planes are the caller's own array, not a copy: kept
        jit = _jit_donated if x.ndim == 1 else jax.jit
        return jit(shard_map(
            per_shard, mesh=plan.mesh,
            in_specs=(in_spec, P()),
            out_specs=(sharded, sharded, P(), P(), P(), P())))

    with spans.span(spans.LAUNCH):
        fn = exec_cache.get_or_build(cache_key, build)
        out, counts, keys, ranks, ovf, stats = fn(xs, jr.key(seed))
    out = out.reshape((p,) + out.shape[naxes:])
    counts = counts.reshape(p)
    if n_pad:   # our sentinel pads may have been counted as keys
        counts = strip_sentinel_counts(out, counts, n_pad=n_pad,
                                       n_restore=n_sent_real)
    return out, counts, keys, ranks, ovf, stats


def run_batched(sort_fn, xs, *, mesh=None, axis_names=("sort",), sizes=None,
                seed=0, n_real=None, local_sort_fn=None, cache_key=None,
                stats_axes=BATCH_AXES, row_args=(), wrap=None):
    """Run B independent shard-level sorts in ONE shard_map launch.

    xs is (B, n): B equal-length key arrays. `sort_fn(local, rng)` is the
    single-request shard function `run` takes; inside the launch it is
    mapped over this shard's (B, n_local) block with one `jax.vmap`. The
    rng is not mapped, so every request draws the same samples as a
    `sort()` of that row alone, and each row of the result is
    bit-identical to it. Returns the raw batched tuple (shards (B, p,
    out_cap), counts (B, p), keys (B, p-1), ranks (B, p-1), overflow (B,),
    stats), the stats laid out by `stats_axes` (the vmap's out_axes for
    them: per-round `SplitterStats` arrays (k, B), `rounds_used` (B,)).

    `row_args`: per-request operands with a leading batch axis, mapped
    with the keys and passed to `sort_fn` after the rng. `wrap`, if
    given, wraps the mapped (B, n_local) function, as the batched audit
    does. Layout: each shard holds a contiguous (B, n_local) column
    block, so request b's keys land on the same shards as an unbatched
    sort of row b. `local_sort_fn` here is the *batched* (B, n) -> (B, n)
    local sort for the p == 1 short-circuit. `cache_key`: see `run`.
    """
    plan = resolve_mesh(mesh, axis_names, sizes)
    p = plan.p
    batch, n = xs.shape
    n_real = n if n_real is None else n_real
    if p == 1:
        with spans.span(spans.LAUNCH):
            out = (local_sort_fn or (lambda v: jnp.sort(v, axis=-1)))(xs)
        return (out[:, None, :], jnp.full((batch, 1), n_real, jnp.int32),
                jnp.zeros((batch, 0), xs.dtype),
                jnp.zeros((batch, 0), jnp.int32),
                jnp.zeros((batch,), jnp.int32), None)
    n_sent_real = None
    n_pad = (-n) % p
    if n_pad:   # per-request sentinel-valued data keys, counted pre-pad
        n_sent_real = jnp.sum((xs == hi_sentinel(xs.dtype)).astype(jnp.int32),
                              axis=1)
        xs = jnp.concatenate(
            [xs, jnp.full((batch, n_pad), hi_sentinel(xs.dtype), xs.dtype)],
            axis=1)
    n_local = (n + n_pad) // p
    xsr = xs.reshape((batch,) + plan.sizes + (n_local,))
    naxes = len(plan.axis_names)

    rows = jax.vmap(sort_fn, in_axes=(0, None) + (0,) * len(row_args),
                    out_axes=(0, 0, 0, 0, 0, stats_axes))

    def batch_fn(local, rng):
        return rows(local, rng, *row_args)

    if wrap is not None:
        batch_fn = wrap(batch_fn)

    def build():
        def per_shard(block, key):
            exec_cache.traces += 1
            local = block.reshape(batch, n_local)
            me = jnp.int32(0)
            for name, size in zip(plan.axis_names, plan.sizes):
                me = me * size + jax.lax.axis_index(name)
            rng = jr.fold_in(key, me)
            out, n_valid, keys, ranks, ovf, stats = batch_fn(local, rng)
            lead = (1,) * naxes
            return (out.reshape((batch,) + lead + out.shape[1:]),
                    jnp.asarray(n_valid, jnp.int32).reshape((batch,) + lead),
                    keys, ranks, ovf, stats)

        sharded = P(None, *plan.axis_names)
        return _jit_donated(shard_map(
            per_shard, mesh=plan.mesh,
            in_specs=(sharded, P()),
            out_specs=(sharded, sharded, P(), P(), P(), P())))

    with spans.span(spans.LAUNCH):
        fn = exec_cache.get_or_build(cache_key, build)
        out, counts, keys, ranks, ovf, stats = fn(xsr, jr.key(seed))
    out = out.reshape((batch, p) + out.shape[1 + naxes:])
    counts = counts.reshape(batch, p)
    if n_pad:   # our sentinel pads may have been counted as keys
        counts = jax.vmap(
            lambda s, c, nr: strip_sentinel_counts(s, c, n_pad=n_pad,
                                                   n_restore=nr)
        )(out, counts, n_sent_real)
    return out, counts, keys, ranks, ovf, stats


def masked_concat(shards, counts, total=None) -> np.ndarray:
    """Concatenate the valid prefixes of all shards into one array.

    Device-side: one scatter over the flattened shard buffer (invalid slots
    dropped via out-of-range indices), replacing the old host Python loop.
    Returns NumPy, like the old `gather_sorted`.
    """
    with spans.span(spans.GATHER):
        return _masked_concat(shards, counts, total)


def _masked_concat(shards, counts, total):
    shards = jnp.asarray(shards)
    counts_np = np.asarray(counts).astype(np.int64)
    total = int(counts_np.sum()) if total is None else total
    if total == 0:
        return np.zeros((0,), shards.dtype)
    p, cap = shards.shape
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(counts_np)[:-1]]),
                          jnp.int32)
    pos = jnp.arange(cap, dtype=jnp.int32)[None, :]
    valid = pos < jnp.asarray(counts_np, jnp.int32)[:, None]
    idx = jnp.where(valid, offsets[:, None] + pos, total)  # `total` => dropped
    out = jnp.zeros((total,), shards.dtype).at[idx.reshape(-1)].set(
        shards.reshape(-1), mode="drop")
    return np.asarray(out)
