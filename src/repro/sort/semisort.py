"""Semisort, group-by aggregation, and top-k on the partitioner substrate
(DESIGN.md Section 10).

The HSS contribution is a high-quality partition found with minimal data
movement; *High-Performance Parallel Semisort* (arXiv 2304.10078) shows
that grouping workloads — equal keys contiguous, no total order across
groups — admit much cheaper plans when the partitioner only has to
co-locate equal keys. This module builds three front doors on the existing
partitioner/exchange seam instead of full sorts:

  semisort(keys)            heavy/light separation: heavy hitters detected
                            from a gathered regular sample of the sorted
                            shards are never exchanged at all — their exact
                            global counts come from one fused psum and they
                            are reported as (key, count) groups; only the
                            light keys ride the splitter histogram path
                            (`Partitioner.partition_sorted`, the relaxed
                            seam with caller-owned local sort + n_valid).
  groupby_aggregate(...)    sum | count | mean | max per distinct key.
                            `count` rides the keys-only semisort (heavy
                            counts are free); value aggregates ride the
                            tagged stable permutation.
  top_k(keys, k)            threshold pruning BEFORE any exchange: each
                            shard keeps only its top-c local suffix
                            (c = min(n_local, round_up(k, 8)) — a key below
                            a shard's local (n_local - c)-rank cannot be in
                            the global top k <= c), so one all_gather of
                            p*c keys replaces the all_to_all over all N.

Dtype-max keys (or NaN payloads mapping onto the hi sentinel) cannot ride
the untagged fast paths — the sentinel is the pad/buffer filler — so
`semisort`/`groupby_aggregate` fall back to the tagged pipeline exactly
like `sort()` does (`make_plan` raises, we re-enter tagged); a totally
sorted output is a valid semisort. `top_k` pads with the LO sentinel
instead, so dtype-max keys are ordinary (winning) keys there.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.contracts import CommsContract, register_contract
from repro.core.common import hi_sentinel, lo_sentinel, round_up
from repro.core.splitters import BATCH_AXES, heavy_candidates
from repro.core.tagging import (
    float32_to_sortable_int32, float64_to_sortable_int64,
    sortable_int32_to_float32, sortable_int64_to_float64)
from repro.kernels import dispatch
from repro.parallel.compat import shard_map
from repro.runtime import chaos
from repro.sort import driver
from repro.sort.adapters import make_plan
from repro.sort.api import (
    _as_spec, _cache_key, _mesh_axes, _mesh_fingerprint, _sort_batched_impl,
    _sort_impl, _with_policies, sort_kv)
from repro.sort.driver import exec_cache
from repro.sort.partitioners import Partitioner, ShardCtx, get_partitioner
from repro.sort.spec import SortSpec

GROUPBY_OPS = ("sum", "count", "mean", "max")


class SemisortStats(NamedTuple):
    """Replicated heavy-hitter payload riding the driver's stats slot."""

    splitter: object       # the light path's SplitterStats
    heavy_keys: object     # (max_heavy,) encoded candidates, sentinel-padded
    heavy_counts: object   # (max_heavy,) exact global counts (0 = pad slot)


class SemisortOutput:
    """Result of keys-only `semisort`.

    light        SortOutput of the light keys (equal keys contiguous — in
                 fact sorted, which the relaxed contract permits).
    heavy_keys   (H,) distinct heavy keys, ascending, original dtype.
    heavy_counts (H,) exact global multiplicities (> 0; psum'd device-side,
                 never subject to exchange capacity).
    n            real input key count.
    `gather()` returns all n keys with equal keys contiguous: the heavy
    groups first (ascending among themselves), then the sorted lights.
    A heavy key never also appears among the lights (its members are
    masked out before the light partition), so contiguity is global.

    heavy_keys/heavy_counts materialize lazily: the front door returns
    while the launch is still in flight, and the device->host copy (plus
    the pad-slot filtering) happens on first access — never on the serving
    hot path (pinned by the `purity` lint in tests/test_analysis.py).
    """

    def __init__(self, light, heavy_keys, heavy_counts, n):
        self.light = light
        self._heavy_keys = heavy_keys
        self._heavy_counts = heavy_counts
        self._decode = None
        self.n = n

    @classmethod
    def deferred(cls, light, raw_keys, raw_counts, n, decode):
        """Wrap still-on-device heavy stats; `decode` maps encoded keys
        back to the caller dtype at materialization time."""
        out = cls(light, raw_keys, raw_counts, n)
        out._decode = decode
        return out

    def _materialize(self):
        if self._decode is not None:
            hk = np.asarray(self._decode(jnp.asarray(self._heavy_keys)))
            hc = np.asarray(self._heavy_counts)
            keep = hc > 0
            self._heavy_keys, self._heavy_counts = hk[keep], hc[keep]
            self._decode = None

    @property
    def heavy_keys(self):
        self._materialize()
        return self._heavy_keys

    @property
    def heavy_counts(self):
        self._materialize()
        return self._heavy_counts

    @property
    def overflow(self):
        return self.light.overflow

    def heavy_total(self) -> int:
        return int(np.sum(self.heavy_counts, dtype=np.int64))

    def gather(self) -> np.ndarray:
        parts = []
        if self.heavy_keys.size:
            parts.append(np.repeat(self.heavy_keys, self.heavy_counts))
        parts.append(np.asarray(self.light.gather()))
        return np.concatenate(parts)

    def groups(self):
        """-> (keys, counts): every distinct key with its multiplicity,
        keys ascending. Raises if the light exchange dropped keys (heavy
        counts are exact by construction)."""
        lk = np.asarray(self.light.gather())
        if lk.shape[0] + self.heavy_total() != self.n:
            raise RuntimeError(
                f"semisort: exchange dropped "
                f"{self.n - lk.shape[0] - self.heavy_total()} light keys "
                "(capacity overflow) — raise out_slack/eps, use "
                "on_overflow='retry', or exchange='allgather'")
        lu, lc = np.unique(lk, return_counts=True)
        keys = np.concatenate([self.heavy_keys, lu])
        counts = np.concatenate([np.asarray(self.heavy_counts, np.int64),
                                 lc.astype(np.int64)])
        order = np.argsort(keys, kind="stable")
        return keys[order], counts[order]


class BatchedSemisortOutput:
    """B independent keys-only semisorts through one launch. heavy_keys /
    heavy_counts keep the full (B, max_heavy) candidate buffers; `request`
    narrows to one request and drops its empty (count 0) slots. Like
    SemisortOutput, the buffers materialize host-side lazily on first
    access, not at launch time."""

    def __init__(self, light, heavy_keys, heavy_counts, n):
        self.light = light
        self._heavy_keys = heavy_keys
        self._heavy_counts = heavy_counts
        self._decode = None
        self.n = n

    @classmethod
    def deferred(cls, light, raw_keys, raw_counts, n, decode):
        out = cls(light, raw_keys, raw_counts, n)
        out._decode = decode
        return out

    def _materialize(self):
        if self._decode is not None:
            self._heavy_keys = np.asarray(
                self._decode(jnp.asarray(self._heavy_keys)))
            self._heavy_counts = np.asarray(self._heavy_counts)
            self._decode = None

    @property
    def heavy_keys(self):
        self._materialize()
        return self._heavy_keys

    @property
    def heavy_counts(self):
        self._materialize()
        return self._heavy_counts

    @property
    def batch(self) -> int:
        return self._heavy_keys.shape[0]   # shape is metadata: no sync

    def request(self, b: int) -> SemisortOutput:
        hk, hc = self.heavy_keys[b], self.heavy_counts[b]
        keep = hc > 0
        return SemisortOutput(self.light.request(b), hk[keep], hc[keep],
                              self.n)

    def gather(self, b: int) -> np.ndarray:
        return self.request(b).gather()


def _heavy_sizing(spec: SortSpec, n_local: int, p: int):
    """Static heavy-detection sizes from the spec knobs.

    A key with global frequency f lands ~f * s_loc / n_local hits in the
    gathered regular sample of the sorted shards (regular sampling of
    sorted data is deterministic to within +-1 per shard, +-p total), so
    the detection threshold f >= heavy_fraction * N / p maps onto
    min_count ~ heavy_fraction * s_tot / p sample hits; we halve it so the
    +-p discretization error cannot miss a genuinely heavy key. False
    positives only cost a (max_heavy,) buffer slot — their exact psum'd
    count keeps them correct. `out_extra` covers the other direction: an
    undetected class (frequency just under the threshold) cannot split
    across splitters, so the light exchange gets additive headroom of two
    boundary runs per destination."""
    s_loc = spec.semisort_sample or max(64, 8 * p)
    s_loc = max(1, min(int(s_loc), n_local))
    s_tot = p * s_loc
    min_count = max(1, int(spec.heavy_fraction * s_tot / (2 * p)))
    max_heavy = round_up(min(s_tot, max(8, s_tot // min_count)), 8)
    out_extra = int(2.0 * spec.heavy_fraction * n_local) + 8
    return s_loc, min_count, max_heavy, out_extra


def _semisort_shard_fn(part, ctx, n_local, s_loc, min_count, max_heavy,
                       ex_cfg, fallback):
    """Shard-resident semisort pipeline for `driver.run` (and, mapped over
    the batch, `run_batched`): local sort -> heavy detection (all_gather'd
    regular sample -> `heavy_candidates` -> exact psum counts) -> mask
    heavies to sentinel -> light partition. `fallback` partitioners
    (multistage) own the whole shard pipeline and take no n_valid, so
    their sentinel tail travels as real max keys and the valid count is
    re-cut at the first sentinel."""
    spec = ctx.spec
    names = ctx.axis_names
    samp_idx = jnp.asarray((np.arange(s_loc) * n_local) // s_loc, jnp.int32)
    sort_local = (spec.local_sort_fn
                  or dispatch.local_sort_fn(spec.kernel_policy))

    def heavy_split(ls):
        sent = hi_sentinel(ls.dtype)
        samp = jnp.take(ls, samp_idx, axis=-1)
        g = jax.lax.all_gather(samp, names)          # (p, s)
        pooled = jnp.sort(g.reshape(-1))
        hkeys = heavy_candidates(pooled, max_heavy=max_heavy,
                                 min_count=min_count)
        llo = jnp.searchsorted(ls, hkeys, side="left")
        lhi = jnp.searchsorted(ls, hkeys, side="right")
        pos = jnp.clip(jnp.searchsorted(hkeys, ls), 0, max_heavy - 1)
        member = jnp.take(hkeys, pos) == ls
        cnt = jnp.where(hkeys == sent, 0, lhi - llo).astype(jnp.int32)
        hcnt = jax.lax.psum(cnt, names)
        is_heavy = member & (ls != sent)
        lights = sort_local(jnp.where(is_heavy, sent, ls))
        n_sent = jnp.sum((ls == sent).astype(jnp.int32), axis=-1)
        n_light = (n_local - n_sent
                   - jnp.sum(is_heavy.astype(jnp.int32), axis=-1))
        return hkeys, hcnt, lights, n_light.astype(jnp.int32)

    def shard_fn(local, rng):
        ls = sort_local(local)
        sent = hi_sentinel(ls.dtype)
        hkeys, hcnt, lights, n_light = heavy_split(ls)
        if fallback:
            out, n_out, keys, ranks, ovf, sstats = part.sharded(
                lights, rng, ctx)
            cut = jnp.searchsorted(out, sent).astype(jnp.int32)
            n_out = jnp.minimum(jnp.asarray(n_out, jnp.int32), cut)
        else:
            out, n_out, keys, ranks, ovf, sstats = part.partition_sorted(
                lights, rng, ctx, n_valid=n_light, ex_cfg=ex_cfg)
        return out, n_out, keys, ranks, ovf, SemisortStats(sstats, hkeys,
                                                           hcnt)

    return shard_fn


def _semisort_fast(x, spec: SortSpec):
    """Keys-only heavy/light semisort. `spec` arrives with tag=False so
    `make_plan` raises on sentinel-valued keys (the caller falls back to
    the tagged pipeline) and never pays duplicate auto-detection."""
    part = get_partitioner(spec.algorithm)
    p, names, sizes = _mesh_axes(spec, part)
    plan = make_plan(x, spec, p)
    enc = plan.encode(x)
    batched = enc.ndim == 2
    n_local = (plan.n + plan.n_pad) // p
    s_loc, min_count, max_heavy, out_extra = _heavy_sizing(spec, n_local, p)
    ctx = ShardCtx(spec=spec, axis_names=names, sizes=sizes, rng=None)
    ex_cfg = dataclasses.replace(spec.exchange_config(), out_extra=out_extra)
    fallback = type(part).sharded is not Partitioner.sharded
    shard_fn = _semisort_shard_fn(part, ctx, n_local, s_loc, min_count,
                                  max_heavy, ex_cfg, fallback)
    base = _cache_key(spec, names, sizes, enc,
                      kind="batched" if batched else "single")
    cache_key = (None if base is None
                 else ("semisort", s_loc, min_count, max_heavy,
                       out_extra) + base)
    if batched:
        p1_sort = dispatch.local_sort_batched_fn(spec.kernel_policy)
        raw = driver.run_batched(
            shard_fn, enc, mesh=spec.mesh, axis_names=names, sizes=sizes,
            seed=spec.seed, n_real=plan.n, local_sort_fn=p1_sort,
            cache_key=cache_key,
            stats_axes=SemisortStats(BATCH_AXES, 0, 0))
        light = plan.decode_batched(raw)
    else:
        p1_sort = (spec.local_sort_fn
                   or dispatch.local_sort_fn(spec.kernel_policy))
        raw = driver.run(
            shard_fn, enc, mesh=spec.mesh, axis_names=names, sizes=sizes,
            seed=spec.seed, n_real=plan.n, local_sort_fn=p1_sort,
            cache_key=cache_key)
        light = plan.decode(raw)
    stats = raw[5]
    if isinstance(stats, SemisortStats):
        # heavy stats stay on device: the device->host copy + decode +
        # pad filtering happen lazily on first heavy_keys/heavy_counts
        # access, so the front door itself never blocks on the launch.
        cls = BatchedSemisortOutput if batched else SemisortOutput
        return cls.deferred(light, stats.heavy_keys, stats.heavy_counts,
                            plan.n, plan._decode_keys)
    # p == 1 short-circuit: fully sorted output, nothing was split
    lead = (enc.shape[0], 0) if batched else (0,)
    hk = np.zeros(lead, x.dtype)
    hc = np.zeros(lead, np.int32)
    if batched:
        return BatchedSemisortOutput(light, hk, hc, plan.n)
    return SemisortOutput(light, hk, hc, plan.n)


def _semisort_tagged(x, spec: SortSpec, batched: bool):
    """Sentinel-collision fallback: the tagged full sort (exactly `sort()`'s
    dtype-max route) — a totally sorted output is a valid semisort with an
    empty heavy set."""
    tag_spec = dataclasses.replace(spec, tag=True)
    if batched:
        out = _with_policies(
            lambda s: _sort_batched_impl(x, s, want_indices=False),
            tag_spec, batched=True)
        b = x.shape[0]
        return BatchedSemisortOutput(
            out, np.zeros((b, 0), np.asarray(x[:0, :0]).dtype),
            np.zeros((b, 0), np.int32), out.n)
    out = _with_policies(lambda s: _sort_impl(x, s, want_indices=False),
                         tag_spec)
    return SemisortOutput(out, np.zeros((0,), np.asarray(x[:0]).dtype),
                          np.zeros((0,), np.int32), out.n)


def semisort(keys, values=None, spec: SortSpec | None = None, **overrides):
    """Group equal keys contiguously across the mesh (no total order
    required across groups — though the light path delivers one anyway).

    Keys-only: returns a SemisortOutput — heavy hitters as exact (key,
    count) groups that never touched the exchange, light keys partitioned
    through the splitter histogram path. With `values`, the grouping must
    carry a payload permutation, which needs the tagged stable pipeline:
    returns (grouped_keys, grouped_values) NumPy arrays (`sort_kv`
    semantics — the relaxed contract permits the fully sorted grouping).
    `stable`/`tag` spec fields are ignored on the keys-only path."""
    spec = _as_spec(spec, overrides)
    if values is not None:
        return sort_kv(keys, values, spec)
    x = jnp.asarray(keys)
    if x.ndim != 1:
        raise ValueError(f"semisort expects a 1-D key array, got {x.shape}")
    fast = dataclasses.replace(spec, tag=False, stable=False)
    try:
        return _semisort_fast(x, fast)
    except ValueError:
        return _semisort_tagged(x, spec, batched=False)


def semisort_batched(xs, spec: SortSpec | None = None, **overrides):
    """B independent keys-only semisorts in ONE shard_map launch: one
    all_gather for heavy detection, one psum for the exact counts, and the
    batched light partition — per request bit-identical to `semisort` on
    that row. Returns a BatchedSemisortOutput."""
    spec = _as_spec(spec, overrides)
    xs = jnp.asarray(xs)
    if xs.ndim != 2:
        raise ValueError(
            f"semisort_batched expects a (B, n) key array, got {xs.shape}")
    fast = dataclasses.replace(spec, tag=False, stable=False)
    try:
        return _semisort_fast(xs, fast)
    except ValueError:
        return _semisort_tagged(xs, spec, batched=True)


def groupby_aggregate(keys, values=None, op: str = "sum",
                      spec: SortSpec | None = None, **overrides):
    """Aggregate `values` per distinct key: -> (uniq_keys, aggregates),
    keys ascending.

    op="count" needs no values and rides the keys-only semisort — heavy
    group counts come straight off the device-side psum; light counts from
    one np.unique over the gathered (exact-checked) light keys. Value ops
    (sum/mean/max) ride the tagged stable permutation; sums/means
    accumulate in int64/float64. Dtype-max keys (the hi-sentinel
    collision) route through tagging automatically, exactly like the sort
    front door."""
    if op not in GROUPBY_OPS:
        raise ValueError(f"op must be one of {GROUPBY_OPS}, got {op!r}")
    spec = _as_spec(spec, overrides)
    if op == "count":
        return semisort(keys, spec=spec).groups()
    if values is None:
        raise ValueError(f"groupby_aggregate(op={op!r}) requires values")
    sk, sv = sort_kv(keys, values, spec)
    uniq, starts = np.unique(sk, return_index=True)
    if op == "max":
        return uniq, np.maximum.reduceat(sv, starts)
    acc = sv.astype(np.float64 if np.issubdtype(sv.dtype, np.floating)
                    else np.int64)
    sums = np.add.reduceat(acc, starts)
    if op == "sum":
        return uniq, sums
    counts = np.diff(np.append(starts, sk.shape[0]))
    return uniq, sums / counts


def _encode_topk(x):
    dtype = jnp.dtype(x.dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        if dtype == jnp.float32:
            return float32_to_sortable_int32(x), 32
        if dtype == jnp.float64:
            return float64_to_sortable_int64(x), 64
        raise ValueError(f"unsupported float dtype {dtype}; cast to "
                         "float32/float64 first")
    if jnp.issubdtype(dtype, jnp.integer):
        return x, 0
    raise ValueError(f"unsupported key dtype {dtype}")


def _decode_topk(enc, float_bits, dtype):
    if float_bits == 32:
        return sortable_int32_to_float32(enc)
    if float_bits == 64:
        return sortable_int64_to_float64(enc)
    return enc.astype(dtype)


def topk_program(mesh_plan, n_local: int, c: int, k: int,
                 kernel_policy: str = "auto", batch: int | None = None):
    """The (unjitted) shard_map program behind `top_k` — exposed so the
    jaxpr-inspection test can pin its collective structure: each shard
    prunes to its top-c local suffix (threshold pruning: a key below the
    local (n_local - c)-rank cannot be in the global top k <= c), then ONE
    all_gather of (p, c) suffixes feeds a replicated merge. No all_to_all,
    and the gather moves p*c keys instead of the full-sort exchange's N.
    With `batch`, the program maps that per-shard function over the
    (batch, n_local) rows of each shard."""
    p = mesh_plan.p
    names = mesh_plan.axis_names

    def per_shard(block):
        ls = dispatch.local_sort(block.reshape(-1), policy=kernel_policy)
        g = jax.lax.all_gather(ls[n_local - c:], names)      # (p, c)
        merged = dispatch.merge_runs(g, policy=kernel_policy)
        return merged[p * c - k:][::-1]

    if batch is None:
        return shard_map(per_shard, mesh=mesh_plan.mesh,
                         in_specs=(P(*names),), out_specs=P())
    return shard_map(jax.vmap(per_shard), mesh=mesh_plan.mesh,
                     in_specs=(P(None, *names),), out_specs=P())


# The wire contract of `topk_program`, proven by the analysis lint on every
# CI run (with gather_widths pinned to the concrete c at check time): the
# pruning claim above, stated as counts. Registered here, next to the
# program it constrains.
register_contract("top_k", CommsContract(
    name="top_k",
    description="shard-local pruning: ZERO all_to_all, exactly ONE "
                "all_gather of the (c,) pruned suffix per shard",
    total_counts={"all_to_all": 0, "all_gather": 1, "psum": 0,
                  "ppermute": 0},
    batch_invariant=("all_gather", "all_to_all", "psum", "ppermute")))


def _topk_impl(enc, k, spec, float_bits, out_dtype, batch=None):
    mesh_plan = driver.resolve_mesh(spec.mesh, (spec.axis_name,), None)
    p = mesh_plan.p
    n = enc.shape[-1]
    if p == 1:
        top = jnp.sort(enc, axis=-1)[..., n - k:][..., ::-1]
        return np.asarray(_decode_topk(top, float_bits, out_dtype))
    if batch is None:
        enc_p, _ = driver.pad_to_shards_lo(enc, p)
        n_local = enc_p.shape[0] // p
        xs = enc_p.reshape(p, n_local)
    else:
        n_pad = (-n) % p
        if n_pad:   # LO pads sort to the front; the top-k suffix is safe
            enc = jnp.concatenate(
                [jnp.full((batch, n_pad), lo_sentinel(enc.dtype), enc.dtype),
                 enc], axis=1)
        n_local = enc.shape[1] // p
        xs = enc.reshape(batch, p, n_local)
    c = min(n_local, round_up(k, 8))
    cache_key = ("topk", batch, k, c, n_local, str(xs.dtype),
                 spec.kernel_policy, mesh_plan.axis_names, mesh_plan.sizes,
                 _mesh_fingerprint(spec), chaos.trace_token())
    fn = exec_cache.get_or_build(
        cache_key,
        lambda: driver._jit_donated(topk_program(
            mesh_plan, n_local, c, k, spec.kernel_policy, batch=batch)))
    return np.asarray(_decode_topk(fn(xs), float_bits, out_dtype))


def top_k(keys, k: int, spec: SortSpec | None = None, **overrides):
    """The k largest keys, descending, as a (k,) NumPy array.

    Never runs a full sort: shards prune to their top-c suffix locally and
    one all_gather of p*c pruned keys replaces the exchange (see
    `topk_program`). Exact for every dtype the sort front door accepts —
    dtype-max keys are fine (padding uses the LO sentinel; a pad colliding
    with a real dtype-min key is indistinguishable by value, which is all
    a values-only top-k returns)."""
    spec = _as_spec(spec, overrides)
    x = jnp.asarray(keys)
    if x.ndim != 1:
        raise ValueError(f"top_k expects a 1-D key array, got {x.shape}")
    n = x.shape[0]
    k = int(k)
    if not 1 <= k <= n:
        raise ValueError(f"k must be in [1, {n}], got {k}")
    enc, float_bits = _encode_topk(x)
    return _topk_impl(enc, k, spec, float_bits, x.dtype)


def top_k_batched(xs, k: int, spec: SortSpec | None = None, **overrides):
    """Per-row top-k of a (B, n) batch in ONE launch: -> (B, k) NumPy
    array, each row descending; bit-identical per row to `top_k`."""
    spec = _as_spec(spec, overrides)
    xs = jnp.asarray(xs)
    if xs.ndim != 2:
        raise ValueError(f"top_k_batched expects (B, n), got {xs.shape}")
    n = xs.shape[1]
    k = int(k)
    if not 1 <= k <= n:
        raise ValueError(f"k must be in [1, {n}], got {k}")
    enc, float_bits = _encode_topk(xs)
    return _topk_impl(enc, k, spec, float_bits, xs.dtype, batch=xs.shape[0])
