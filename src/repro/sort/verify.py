"""Device-side output verification for the sort pipeline (DESIGN.md Sec. 9).

The paper's contract is that the output is a (1+eps)-balanced, globally
sorted permutation of the input. Nothing in the pipeline *checked* that at
runtime before this module: a silently-corrupting kernel, exchange, or
recovery path would ship wrong answers. `audited(sort_fn)` wraps the
shard-level pipeline with a postcondition audit that runs INSIDE the same
shard_map launch, costing O(n/p) local compute plus exactly one extra fused
psum (and one ppermute of edge keys):

  * multiset fingerprint — an order-independent keyed hash-sum over the
    encoded keys, compared input-vs-output. Each key contributes
    mix32(key ^ seed_l) to lane l; lanes are summed per shard with uint32
    wraparound and psum-reduced, so equal multisets give equal lanes
    regardless of how keys moved between shards. "cheap" keeps 2 lanes
    (64 fingerprint bits), "full" keeps 4 (128 bits). On the tagged path
    the hashed word is the packed (key << b) | index, so the fingerprint
    covers key/value PAIRS — a payload sent with the wrong key changes the
    packed word and therefore the fingerprint (the `sort_kv` guarantee).
  * count conservation — psum of the per-shard valid counts must equal the
    padded input length (drops anywhere show up here).
  * per-shard sortedness — adjacent-pair violations in each valid prefix.
  * cross-shard boundary order — one ppermute sends each shard's last
    valid key to its successor, which checks it against its own first key.
    An empty shard forwards the lo sentinel (vacuous), which the splitter
    range check closes: shard i must hold keys in [s_{i-1}, s_i) under the
    exchange's searchsorted-left semantics, so out-of-range keys are
    caught even across empty shards. Multistage publishes no splitters, so
    it swaps the ppermute for a tiny all_gather of edge keys and checks
    first_i against the running max of predecessors' lasts — complete even
    across empty shards.

The audit result rides the driver's replicated stats slot as a
`(stats, audit_vec)` pair — the 6-tuple contract and out_specs are
untouched. The front door (repro.sort.api) unwraps it, materializes it
host-side ONCE per launch (`finalize` -> AuditReport), and applies
`SortSpec.on_verify_failure`. The chaos `corrupt_at` fault injects a
bit-flip between the pipeline and the audit (`_corrupt`), which is how the
tests prove detection without a real miscompile.

Collision bound: a corruption escapes lane l only if the uint32 hash-sums
collide, ~2^-32 per lane for the avalanche mixer; tiers stack lanes to
2^-64 ("cheap") / 2^-128 ("full"). Structural violations (ordering,
counts, range) are checked exactly, not probabilistically.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.common import hi_sentinel, lo_sentinel
from repro.runtime import spans

TIERS = ("off", "cheap", "full")
_LANES = {"cheap": 2, "full": 4}
_GOLD = 0x9E3779B9


class VerificationError(RuntimeError):
    """The device-side audit rejected a sort output (and the
    on_verify_failure policy could not recover). Carries the AuditReport."""

    def __init__(self, msg: str, report: "AuditReport | None" = None):
        super().__init__(msg)
        self.report = report


class BatchVerificationError(VerificationError):
    """Batched audit failure: carries the decoded BatchedSortOutput and the
    per-row verdicts so the serving layer can serve the rows that verified
    and fail only the corrupted ones."""

    def __init__(self, msg: str, report: "AuditReport", output):
        super().__init__(msg, report)
        self.output = output
        self.row_ok = np.atleast_1d(report.row_ok)


class ImbalanceError(RuntimeError):
    """The partition-quality SLO was violated and neither duplicate
    tagging nor bonus refinement brought achieved_imbalance under it."""

    def __init__(self, msg: str, achieved: float, slo: float):
        super().__init__(msg)
        self.achieved = achieved
        self.slo = slo


def lanes_for(tier: str) -> int:
    return _LANES[tier]


def audit_width(tier: str) -> int:
    """uint32 words per request in the audit vector."""
    return 2 * lanes_for(tier) + 4


def _mix32(v, seed: int):
    """32-bit avalanche mixer (the fmix32 finalizer) under a lane seed."""
    v = v ^ jnp.uint32(seed & 0xFFFFFFFF)
    v = (v ^ (v >> 16)) * jnp.uint32(0x85EBCA6B)
    v = (v ^ (v >> 13)) * jnp.uint32(0xC2B2AE35)
    return v ^ (v >> 16)


def fingerprint_lanes(x, n_lanes: int, mask=None):
    """Keyed multiset fingerprint of the last axis of `x`: (..., L) uint32
    wraparound hash-sums, one per lane. Equal multisets (per leading index)
    give equal lanes; sums commute with psum, so sharded multisets reduce
    with one collective. 64-bit words hash as two mixed 32-bit halves."""
    x = jnp.asarray(x)
    if jnp.dtype(x.dtype).itemsize == 8:
        lo = (x & jnp.asarray(0xFFFFFFFF, x.dtype)).astype(jnp.uint32)
        hi = (x >> 32).astype(jnp.uint32)
    else:
        lo = x.astype(jnp.uint32)
        hi = None
    lanes = []
    for lane in range(n_lanes):
        seed = (0xA0761D64 + _GOLD * lane) & 0xFFFFFFFF
        h = _mix32(lo, seed)
        if hi is not None:
            h = h + _mix32(hi, seed ^ 0x85EBCA77) * jnp.uint32(0x27D4EB2F)
        if mask is not None:
            h = jnp.where(mask, h, jnp.uint32(0))
        lanes.append(jnp.sum(h, axis=-1, dtype=jnp.uint32))
    return jnp.stack(lanes, axis=-1)


def _shard_index(axis_names, sizes):
    me = jnp.int32(0)
    for name, size in zip(axis_names, sizes):
        me = me * size + jax.lax.axis_index(name)
    return me


def _edges(out, n_valid):
    """Per-row (first, last) valid keys; empty rows yield the vacuous
    (hi, lo) sentinel pair. out (B, cap), n_valid (B,)."""
    dt = out.dtype
    last_at = jnp.take_along_axis(
        out, jnp.maximum(n_valid - 1, 0)[:, None], axis=1)[:, 0]
    first = jnp.where(n_valid > 0, out[:, 0], hi_sentinel(dt))
    last = jnp.where(n_valid > 0, last_at, lo_sentinel(dt))
    return first, last


def _gather_global(v, axis_names):
    """(B,) per shard -> (p, B) in global row-major shard order."""
    for name in reversed(tuple(axis_names)):
        v = jax.lax.all_gather(v, name)
    return v.reshape((-1,) + v.shape[len(axis_names):])


def _boundary_viol(out, n_valid, me, p, axis_names):
    """Per-shard contribution to the cross-shard boundary check, (B,)
    uint32 (summed exactly once by the fused psum)."""
    first, last = _edges(out, n_valid)
    if len(axis_names) == 1:
        perm = [(i, i + 1) for i in range(p - 1)]
        prev_last = jax.lax.ppermute(last, axis_names[0], perm)
        bad = (me > 0) & (prev_last > first)
    else:
        # multistage: no splitters to range-check, so use the complete
        # running-max form over a tiny all_gather of edge keys instead
        lasts = _gather_global(last, axis_names)            # (p, B)
        prefix = jax.lax.cummax(lasts, axis=0)
        prev_max = prefix[jnp.maximum(me - 1, 0)]
        bad = (me > 0) & (first < prev_max)
    return bad.astype(jnp.uint32)


def _range_viol(out, valid, keys, me, p):
    """Splitter-range check: shard i holds keys in [s_{i-1}, s_i) by the
    exchange's searchsorted-left slicing (last shard unbounded above, so
    sentinel pads pass). Closes the empty-shard hole the edge ppermute
    leaves. keys (B, p-1) — empty for multistage (statically skipped)."""
    if keys.shape[-1] == 0:
        return jnp.zeros((out.shape[0],), jnp.uint32)
    lo = jnp.where(me > 0, keys[:, jnp.maximum(me - 1, 0)],
                   lo_sentinel(out.dtype))
    hi = keys[:, jnp.minimum(me, p - 2)]
    bad = (out < lo[:, None]) | ((me < p - 1) & (out >= hi[:, None]))
    return jnp.sum((bad & valid).astype(jnp.uint32), axis=-1)


def _apply_corrupt(out, local, n_valid, me, p, axis_names, corrupt):
    """chaos `corrupt_at` seam: XOR `corrupt_bit` into the first key of
    the LAST shard (provably non-empty — the global max routes there under
    searchsorted-left slicing) for every armed row. With a corrupt_key the
    flip targets only rows whose input contains it (matched in the encoded
    key domain — exact for untagged integer keys), which is what lets the
    serving smoke corrupt one request and demand its batchmates stay
    bit-exact. The extra psum below exists only in corrupt traces, which
    are never cached (repro.sort.api)."""
    bit, key = corrupt
    if key is None:
        hit = jnp.ones((local.shape[0],), bool)
    else:
        present = jnp.any(local == jnp.asarray(key, local.dtype), axis=-1)
        hit = jax.lax.psum(present.astype(jnp.int32), tuple(axis_names)) > 0
    do = (me == p - 1) & hit & (n_valid > 0)
    flip = jnp.where(do, jnp.asarray(1, out.dtype) << bit,
                     jnp.asarray(0, out.dtype))
    return out.at[:, 0].set(out[:, 0] ^ flip)


def audited(sort_fn, *, tier: str, axis_names, sizes, batched: bool,
            corrupt=None):
    """Wrap a shard-level `sort_fn` (single or batched 6-tuple contract)
    with the fused postcondition audit. The returned wrapper's stats slot
    becomes `(stats, audit_vec)` where audit_vec is (B, 2L+4) uint32
    ((1, 2L+4) on the single path), psum-reduced and replicated:

        [0:L]    input fingerprint lanes     [2L]    output key count
        [L:2L]   output fingerprint lanes    [2L+1]  sortedness violations
                                             [2L+2]  boundary violations
                                             [2L+3]  range violations
    """
    nl = lanes_for(tier)
    axis_names = tuple(axis_names)
    p = int(np.prod(tuple(sizes)))

    def wrapped(local, rng):
        out, n_valid, keys, ranks, ovf, stats = sort_fn(local, rng)
        with spans.scope(spans.AUDIT):
            o, vec = audit(local, out, n_valid, keys)
        out = o if batched else o[0]
        return out, n_valid, keys, ranks, ovf, (stats, vec)

    def audit(local, out, n_valid, keys):
        if batched:
            o, loc = out, local
            nv = jnp.asarray(n_valid, jnp.int32)
            k = keys
        else:
            o, loc = out[None], local[None]
            nv = jnp.asarray(n_valid, jnp.int32).reshape(1)
            k = keys[None]
        me = _shard_index(axis_names, sizes)
        in_lanes = fingerprint_lanes(loc, nl)
        if corrupt is not None:
            o = _apply_corrupt(o, loc, nv, me, p, axis_names, corrupt)
        cap = o.shape[-1]
        valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < nv[:, None]
        # hash the output in the INPUT's encoding dtype: under jax x64 the
        # pipeline may promote buffers to int64 while values stay put, and
        # 8-byte words hash via the two-half path — a pure dtype change
        # must not read as a multiset mismatch
        out_lanes = fingerprint_lanes(o.astype(loc.dtype), nl, mask=valid)
        order = jnp.sum(((o[:, 1:] < o[:, :-1]) & valid[:, 1:])
                        .astype(jnp.uint32), axis=-1)
        boundary = _boundary_viol(o, nv, me, p, axis_names)
        rng_viol = _range_viol(o, valid, k, me, p)
        vec = jnp.concatenate(
            [in_lanes, out_lanes,
             jnp.stack([nv.astype(jnp.uint32), order, boundary, rng_viol],
                       axis=-1)], axis=-1)
        # the violation words can promote under jax x64, dragging the whole
        # vec to 64-bit — but the lane algebra NEEDS the psum to wrap mod
        # 2^32 (per-shard lane sums already wrapped; a 64-bit reduction
        # makes identical multisets disagree by multiples of 2^32)
        return o, jax.lax.psum(vec.astype(jnp.uint32), axis_names)

    return wrapped


def split_raw(raw):
    """Unwrap the `(stats, audit_vec)` stats slot an audited launch
    returns -> (plain 6-tuple, audit_vec)."""
    out, counts, keys, ranks, ovf, packed = raw
    stats, vec = packed
    return (out, counts, keys, ranks, ovf, stats), vec


def audit_p1(enc, shards, counts, tier: str):
    """Post-hoc audit for the driver's p == 1 short-circuit, which bypasses
    the shard-level pipeline entirely (no collectives, no pads: n_pad is
    (-n) % 1 == 0). Same vector layout as the fused audit; boundary and
    range words are structurally zero."""
    nl = lanes_for(tier)
    encr = jnp.asarray(enc)
    rows = (jnp.asarray(shards).astype(encr.dtype)   # see audited(): dtype-
            .reshape(-1, np.shape(shards)[-1]))      # promotion isn't loss
    cnt = jnp.asarray(counts, jnp.int32).reshape(-1)
    encr = encr.reshape(rows.shape[0], -1)
    valid = jnp.arange(rows.shape[-1], dtype=jnp.int32)[None, :] \
        < cnt[:, None]
    in_lanes = fingerprint_lanes(encr, nl)
    out_lanes = fingerprint_lanes(rows, nl, mask=valid)
    order = jnp.sum(((rows[:, 1:] < rows[:, :-1]) & valid[:, 1:])
                    .astype(jnp.uint32), axis=-1)
    zeros = jnp.zeros_like(order)
    return jnp.concatenate(
        [in_lanes, out_lanes,
         jnp.stack([cnt.astype(jnp.uint32), order, zeros, zeros], axis=-1)],
        axis=-1).astype(jnp.uint32)   # keep mod-2^32 algebra under jax x64


@dataclasses.dataclass
class AuditReport:
    """Host-side verdict of one audited launch (see `finalize`). On the
    batched path every field is a (B,) array and `row_ok` gives per-row
    verdicts; `row(b)` views one request's verdict (what
    `BatchedSortOutput.request` attaches)."""

    tier: str
    batched: bool
    n_expected: int
    count: Any
    fingerprint_ok: Any
    count_ok: Any
    order_violations: Any
    boundary_violations: Any
    range_violations: Any
    row_ok: Any
    achieved_imbalance: Any = None

    @property
    def ok(self) -> bool:
        return bool(np.all(self.row_ok))

    def row(self, b: int) -> "AuditReport":
        if not self.batched:
            return self
        pick = lambda v: None if v is None else v[b]
        return AuditReport(
            tier=self.tier, batched=False, n_expected=self.n_expected,
            count=pick(self.count), fingerprint_ok=pick(self.fingerprint_ok),
            count_ok=pick(self.count_ok),
            order_violations=pick(self.order_violations),
            boundary_violations=pick(self.boundary_violations),
            range_violations=pick(self.range_violations),
            row_ok=pick(self.row_ok),
            achieved_imbalance=pick(self.achieved_imbalance))

    def describe(self) -> str:
        if self.ok:
            return f"verify={self.tier}: ok"
        bad = np.flatnonzero(~np.atleast_1d(self.row_ok))
        parts = []
        if not np.all(self.fingerprint_ok):
            parts.append("multiset fingerprint mismatch")
        if not np.all(self.count_ok):
            lost = self.n_expected - np.atleast_1d(self.count)[bad]
            parts.append(f"count mismatch ({lost.max()} keys lost)")
        for name, v in (("sortedness", self.order_violations),
                        ("boundary", self.boundary_violations),
                        ("range", self.range_violations)):
            tot = int(np.sum(np.atleast_1d(v)))
            if tot:
                parts.append(f"{tot} {name} violations")
        where = (f"rows {bad.tolist()}" if self.batched else "output")
        return (f"verify={self.tier} FAILED on {where}: "
                + "; ".join(parts))


def finalize(audit_vec, *, tier: str, n_expected: int,
             batched: bool) -> AuditReport:
    """Materialize an audit vector (ONE host sync per verified launch) and
    judge it. `n_expected` is the padded per-request key count — the exact
    value the fused count word must equal when nothing was dropped."""
    lanes = lanes_for(tier)
    v = np.asarray(jax.device_get(audit_vec)).astype(np.uint64)
    v = v.reshape(-1, audit_width(tier))
    fp_ok = np.all(v[:, :lanes] == v[:, lanes:2 * lanes], axis=1)
    count = v[:, 2 * lanes].astype(np.int64)
    count_ok = count == n_expected
    order = v[:, 2 * lanes + 1]
    boundary = v[:, 2 * lanes + 2]
    rng_ = v[:, 2 * lanes + 3]
    row_ok = fp_ok & count_ok & (order == 0) & (boundary == 0) & (rng_ == 0)
    sq = (lambda a: a) if batched else (lambda a: a[0])
    return AuditReport(
        tier=tier, batched=batched, n_expected=int(n_expected),
        count=sq(count), fingerprint_ok=sq(fp_ok), count_ok=sq(count_ok),
        order_violations=sq(order), boundary_violations=sq(boundary),
        range_violations=sq(rng_), row_ok=sq(row_ok))
