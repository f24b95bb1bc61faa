"""Dtype and duplicate-tagging adapters for the `repro.sort` front-door.

The core partitioners operate on 1-D arrays of *distinct, integer-ordered*
keys (the paper's analysis assumes distinct keys; XLA sentinels assume
integer-comparable buffers). This module bridges arbitrary user inputs onto
that contract and back:

  * float keys are routed through the order-preserving IEEE-754 bijection
    (repro.core.tagging): float32 <-> int32, float64 <-> int64 (jax x64);
  * duplicate keys — always present for `stable=True`, `argsort`,
    `sort_kv`, and auto-detected otherwise where a tag fits — are made
    distinct by implicit tagging (paper Section 6.3): keys are rebased to
    their observed range and packed as (key << b) | global_index, so the
    tag doubles as the argsort permutation on the way out;
  * non-divisible inputs are padded *before* packing with the maximum real
    key, so pads sort to the global tail and the driver trims them.

An `AdapterPlan` is built per call (it reads the key range — one O(n)
device reduction whose two scalars sync to host — and, only where a tag
would fit, a duplicate-detection sort whose flag syncs too) and exposes
`encode(x)` / `decode(raw)`, both device-side. The raw core path
(`repro.core.hss_sort` et al.) remains available for callers that cannot
afford even the scalar syncs.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.common import (RECORD_BYTES, RECORD_KEY_WORDS, RECORD_WORDS,
                               hi_sentinel)
from repro.core.tagging import (
    float32_to_sortable_int32, float64_to_sortable_int64,
    sortable_int32_to_float32, sortable_int64_to_float64, tag_bits)
from repro.runtime import spans
from repro.sort.spec import SortSpec


class SortOutput:
    """Decoded result of `repro.sort.sort`.

    shards   (p, cap) sorted keys per shard, original dtype; slots past
             counts[i] hold the dtype's +sentinel.
    counts   (p,) valid keys per shard (pads already trimmed; sums to n
             when overflow == 0).
    indices  (p, cap) original global positions of the keys (the argsort
             permutation), -1 past counts[i]; None when the sort ran
             untagged.
    overflow dropped-key count (0 => exact, the contract callers check).
    splitter_keys / splitter_ranks / stats  diagnostics from the
             partitioner (splitter keys decoded back to the key domain).
    recovery overflow-recovery stats (repro.sort.RecoveryStats) attached
             by the `on_overflow="retry"` policy; None otherwise.
    audit    verification verdict (repro.sort.verify.AuditReport) attached
             when the sort ran with `verify != "off"`; None otherwise.
    n        number of real input keys.
    """

    recovery = None
    audit = None
    _audit_vec = None
    _audit_expected = 0

    def __init__(self, shards, counts, indices, overflow, splitter_keys,
                 splitter_ranks, stats, n):
        self.shards = shards
        self.counts = counts
        self.indices = indices
        self.overflow = overflow
        self.splitter_keys = splitter_keys
        self.splitter_ranks = splitter_ranks
        self.stats = stats
        self.n = n

    def gather(self) -> np.ndarray:
        """All keys globally sorted, as one (n,) NumPy array."""
        from repro.sort.driver import masked_concat
        return masked_concat(self.shards, self.counts)

    def gather_indices(self) -> np.ndarray:
        """The argsort permutation, as one (n,) NumPy array."""
        if self.indices is None:
            raise ValueError("sort ran untagged: no indices were tracked "
                             "(use stable=True / tag=True, or argsort())")
        from repro.sort.driver import masked_concat
        return masked_concat(self.indices, self.counts)


class BatchedSortOutput:
    """Decoded result of `repro.sort.sort_batched`: B equal-length requests
    sorted independently through one launch.

    Every per-request array of SortOutput gains a leading batch axis:
    shards (B, p, cap), counts (B, p), indices (B, p, cap) | None,
    overflow (B,), splitter_keys/splitter_ranks (B, p-1), stats batched
    per-request (SplitterStats rows of shape (k, B)), n = per-request real
    key count. `request(b)` views one request as a regular SortOutput;
    `recovery` (batch-level overflow-recovery stats, see SortOutput) is
    carried onto every view, and `audit` (batch-level AuditReport with
    per-row verdicts) is narrowed to the request's own row.
    """

    recovery = None
    audit = None
    _audit_vec = None
    _audit_expected = 0

    def __init__(self, shards, counts, indices, overflow, splitter_keys,
                 splitter_ranks, stats, n):
        self.shards = shards
        self.counts = counts
        self.indices = indices
        self.overflow = overflow
        self.splitter_keys = splitter_keys
        self.splitter_ranks = splitter_ranks
        self.stats = stats
        self.n = n

    @property
    def batch(self) -> int:
        return self.shards.shape[0]

    def request(self, b: int) -> SortOutput:
        """Request b's result as a SortOutput view (stats stay batched)."""
        out = SortOutput(
            self.shards[b], self.counts[b],
            None if self.indices is None else self.indices[b],
            self.overflow[b], self.splitter_keys[b], self.splitter_ranks[b],
            self.stats, self.n)
        out.recovery = self.recovery
        if self.audit is not None:
            out.audit = self.audit.row(b)
        return out

    def gather(self, b: int) -> np.ndarray:
        """Request b's keys, globally sorted, as one (n,) NumPy array."""
        return self.request(b).gather()

    def gather_indices(self, b: int) -> np.ndarray:
        """Request b's argsort permutation as one (n,) NumPy array."""
        return self.request(b).gather_indices()

    def gather_all(self) -> list:
        """Every request gathered, in batch order."""
        return [self.gather(b) for b in range(self.batch)]


@dataclasses.dataclass
class AdapterPlan:
    spec: SortSpec
    p: int
    n: int                 # real keys (per request on the batched path)
    n_pad: int
    out_dtype: Any         # user-facing key dtype
    float_bits: int        # 0 | 32 | 64
    tagged: bool
    tag_b: int = 0
    key_min: int = 0       # rebase offset in the (encoded-)integer domain
    key_max: int = 0
    pack_dtype: Any = None
    batched: bool = False  # plan built over a (B, n) request batch
    _enc: Any = None       # bijection result cached by make_plan (tagged)

    def encode(self, x: jax.Array) -> jax.Array:
        """Keys -> the distinct-integer core domain. x is (n,) — or (B, n)
        for a batched plan, where every row is encoded identically (shared
        rebase offset; per-row index tags, so each row's tags decode to
        that request's own argsort permutation)."""
        if self._enc is not None:
            enc = self._enc
        elif self.float_bits == 32:
            enc = float32_to_sortable_int32(x)
        elif self.float_bits == 64:
            enc = float64_to_sortable_int64(x)
        else:
            enc = x
        if not self.tagged:
            # pads (hi sentinel) are appended by the driver
            return enc
        # pack device-side: the rebased key fits the pack dtype by
        # construction (make_plan checked the bit budget). Rebase in
        # whichever domain is wide enough — the key dtype itself when the
        # pack dtype is no wider (keeps uint key_min representable), the
        # pack dtype otherwise (avoids overflow of signed-min + range).
        dt = jnp.dtype(self.pack_dtype)
        if self.n_pad:   # pads = max real key; sort to the global tail
            pad_shape = enc.shape[:-1] + (self.n_pad,)
            pad = jnp.full(pad_shape, jnp.asarray(self.key_max, enc.dtype))
            enc = jnp.concatenate([enc, pad], axis=-1)
        wide = enc.astype(dt) if dt.itemsize > enc.dtype.itemsize else enc
        e = (wide - jnp.asarray(self.key_min, wide.dtype)).astype(dt)
        return (e << self.tag_b) | jnp.arange(e.shape[-1], dtype=dt)

    def encode_probes(self, probes) -> jax.Array:
        """Warm-start probes (original key domain) -> encoded domain."""
        probes = jnp.asarray(probes)
        if self.float_bits == 32:
            probes = float32_to_sortable_int32(probes)
        elif self.float_bits == 64:
            probes = float64_to_sortable_int64(probes)
        if not self.tagged:
            return probes
        e = np.asarray(probes).astype(np.int64)
        return jnp.asarray(((e - self.key_min) << self.tag_b)
                           .astype(self.pack_dtype))

    def decode(self, raw) -> SortOutput:
        shards, counts, skeys, sranks, overflow, stats = raw
        cap = shards.shape[1]
        valid = jnp.arange(cap, dtype=jnp.int32)[None, :] \
            < jnp.asarray(counts, jnp.int32)[:, None]
        indices = None
        if self.tagged:
            mask = (1 << self.tag_b) - 1
            raw_idx = shards & mask
            if self.n_pad:
                # pads carry indices >= n; they may have been counted as
                # valid by the exchange — exact even under key drops
                pads = valid & (raw_idx >= self.n)
                counts = (jnp.asarray(counts, jnp.int32)
                          - jnp.sum(pads, axis=1).astype(jnp.int32))
                valid = jnp.arange(cap, dtype=jnp.int32)[None, :] \
                    < counts[:, None]
            indices = jnp.where(valid, raw_idx, -1)
            shards = self._unrebase(shards >> self.tag_b)
            if skeys.size:
                skeys = self._unrebase(skeys >> self.tag_b)
        shards = self._decode_keys(shards)
        skeys = self._decode_keys(skeys) if skeys.size else skeys
        shards = jnp.where(valid, shards, hi_sentinel(self.out_dtype))
        return SortOutput(shards, counts, indices, overflow, skeys, sranks,
                          stats, self.n)

    def decode_batched(self, raw) -> "BatchedSortOutput":
        """Decode the raw batched driver tuple (leading (B,) on every
        per-request array) into a BatchedSortOutput. Same steps as `decode`
        with the batch axis carried through."""
        shards, counts, skeys, sranks, overflow, stats = raw
        cap = shards.shape[-1]
        counts = jnp.asarray(counts, jnp.int32)
        valid = jnp.arange(cap, dtype=jnp.int32)[None, None, :] \
            < counts[:, :, None]
        indices = None
        if self.tagged:
            mask = (1 << self.tag_b) - 1
            raw_idx = shards & mask
            if self.n_pad:
                pads = valid & (raw_idx >= self.n)
                counts = counts - jnp.sum(pads, axis=2).astype(jnp.int32)
                valid = jnp.arange(cap, dtype=jnp.int32)[None, None, :] \
                    < counts[:, :, None]
            indices = jnp.where(valid, raw_idx, -1)
            shards = self._unrebase(shards >> self.tag_b)
            if skeys.size:
                skeys = self._unrebase(skeys >> self.tag_b)
        shards = self._decode_keys(shards)
        skeys = self._decode_keys(skeys) if skeys.size else skeys
        shards = jnp.where(valid, shards, hi_sentinel(self.out_dtype))
        return BatchedSortOutput(shards, counts, indices, overflow, skeys,
                                 sranks, stats, self.n)

    def _unrebase(self, rebased):
        """rebased (pack dtype, in [0, key_range]) -> original key domain.

        The addition must run in the output integer domain: key_min may not
        be representable in the pack dtype (uint keys above the signed max).
        """
        if self.float_bits:   # encoded-int domain == pack dtype; min fits
            return rebased + self.key_min
        return (rebased.astype(self.out_dtype)
                + jnp.asarray(self.key_min, self.out_dtype))

    def _decode_keys(self, enc):
        if self.float_bits == 32:
            return sortable_int32_to_float32(enc.astype(jnp.int32))
        if self.float_bits == 64:
            return sortable_int64_to_float64(enc)
        return enc.astype(self.out_dtype)


def _needs_tags(spec: SortSpec, want_indices: bool):
    """-> (wanted, required), wanted None where only duplicate detection
    can say. Required tagging errors out when the packing budget does not
    fit; merely wanted tagging (auto duplicate detection) falls back to
    untagged, which still sorts correctly — duplicates only cost load
    balance, and that surfaces through the overflow counter."""
    if spec.tag is not None:
        if not spec.tag and want_indices:
            raise ValueError("argsort/sort_kv require tagging (tag=False set)")
        return spec.tag, spec.tag
    if spec.stable or want_indices:
        return True, True
    return None, False


@jax.jit
def detect_duplicates(x: jax.Array) -> jax.Array:
    """Whether some row of x (the last axis) holds a key twice: its sort,
    compare and any as one program, `jit_detect_duplicates` in a trace."""
    s = jnp.sort(x, axis=-1)
    return jnp.any(s[..., 1:] == s[..., :-1])


@jax.jit
def key_range(enc: jax.Array) -> jax.Array:
    """[min, max] of every key in enc, so one read brings both to the
    host: one program, `jit_key_range` in a trace."""
    return jnp.stack([jnp.min(enc), jnp.max(enc)])


def make_plan(x: jax.Array, spec: SortSpec, p: int,
              want_indices: bool = False, records: bool = False):
    """Inspect the input and decide bijection/tagging/padding. Host-side.
    The key range is read first; auto duplicate detection (`tag=None`)
    runs only where the range and p leave room for a tag, since only
    there can it change the plan. With `records`, x is record word planes
    and the plan a `RecordPlan`: no detection, no key range, no host sync.

    x may be (n,) or, for the batched engine, (B, n): one plan serves the
    whole batch — the key range (and so the rebase offset and packing
    budget) is taken over all B requests jointly, while tag indices stay
    per-request (`encode` broadcasts one arange over rows).
    """
    if records:
        return RecordPlan.of(x, spec, p)
    n = x.shape[-1]
    if n == 0 or x.size == 0:
        raise ValueError("cannot sort an empty array")
    n_pad = (-n) % p
    dtype = jnp.dtype(x.dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        if dtype == jnp.float32:
            float_bits = 32
        elif dtype == jnp.float64:
            float_bits = 64
            if not jax.config.jax_enable_x64:
                raise ValueError("float64 keys need jax x64 enabled "
                                 "(they map onto sortable int64)")
        else:
            raise ValueError(f"unsupported float dtype {dtype}; cast to "
                             "float32/float64 first")
    elif jnp.issubdtype(dtype, jnp.integer):
        float_bits = 0
    else:
        raise ValueError(f"unsupported key dtype {dtype}")
    plan = AdapterPlan(spec=spec, p=p, n=n, n_pad=n_pad, out_dtype=dtype,
                       float_bits=float_bits, tagged=False,
                       batched=x.ndim == 2)

    if float_bits == 32:
        enc = float32_to_sortable_int32(x)
        enc_sentinel = int(jnp.iinfo(jnp.int32).max)
    elif float_bits == 64:
        enc = float64_to_sortable_int64(x)
        enc_sentinel = int(jnp.iinfo(jnp.int64).max)
    else:
        enc = x
        enc_sentinel = int(jnp.iinfo(dtype).max)
    plan._enc = enc if float_bits else None   # reuse bijection in encode()

    wanted, required = _needs_tags(spec, want_indices)
    key_min, key_max = map(int, np.asarray(key_range(enc)))
    if key_max == enc_sentinel:
        # keys whose (encoded) value equals the hi sentinel the untagged
        # pipeline uses for padding/buffers would be silently dropped —
        # dtype-max ints, or the float NaN payload that maps onto it;
        # tagging rebases keys below the sentinel, so force it (or refuse).
        if spec.tag is False:
            raise ValueError(
                f"keys contain the {dtype} sentinel value (dtype max, or a "
                "NaN payload mapping onto it) reserved by the untagged path "
                "(tag=False): remove those keys or drop tag=False")
        wanted = required = True
    if wanted is False:
        return plan

    # tagging: compute the packing budget from the observed key range
    key_bits = max(1, int(key_max - key_min).bit_length())
    n_local = (n + n_pad) // p
    b = tag_bits(p, n_local)
    total = key_bits + b
    if total <= 30:           # one bit of headroom below the int32 sentinel
        pack_dtype = np.int32
    elif total <= 62 and jax.config.jax_enable_x64:
        pack_dtype = np.int64
    elif not required:
        return plan           # auto-tagging doesn't fit: sort untagged
    elif total <= 62:
        raise ValueError(
            f"key range needs {key_bits} bits + {b} tag bits > 30: "
            "enable jax x64 for int64 packing, or pass tag=False for "
            "known-distinct keys")
    else:
        raise ValueError(f"key_bits={key_bits} + tag_bits={b} > 62: "
                         "compress the key range before sorting")
    if wanted is None:
        # auto duplicate detection, only now that a tag fits: a device-side
        # sort + adjacent-equal check (only a scalar crosses to host);
        # override with tag=False when keys are known-distinct. On a (B, n)
        # batch, rows sort independently — duplicates only matter within a
        # request, but any duplicated row tags the whole batch (one plan).
        with spans.span(spans.DETECT):
            if not bool(detect_duplicates(x)):
                return plan
    plan.tagged = True
    plan.tag_b = b
    plan.key_min = key_min
    plan.key_max = key_max
    plan.pack_dtype = pack_dtype
    plan._enc = enc        # reuse the bijection result in encode()
    return plan


# -- Sort Benchmark records (repro.sort.sort_records) ----------------------

@jax.jit
def pack_records(records) -> jax.Array:
    """(n, 100) uint8 records (NumPy or on the device) -> their (25, n)
    uint32 word planes on the device, each word four bytes big-endian, so
    unsigned word order is memcmp order: one program, `jit_pack_records`
    in a trace."""
    b = jnp.asarray(records, jnp.uint8).reshape(
        -1, RECORD_WORDS, 4).astype(jnp.uint32)
    w = (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]
    return w.T


def unpack_records(words) -> np.ndarray:
    """(25, n) uint32 word planes -> (n, 100) uint8 records (host)."""
    w = np.asarray(words, np.uint32)
    return (np.ascontiguousarray(w.T).astype(">u4").view(np.uint8)
            .reshape(-1, RECORD_BYTES))


class RecordSortOutput:
    """Result of `repro.sort.sort_records`, all of it on the device until a
    caller gathers.

    shards   (p, 25, out_cap) uint32: each chip's records as word planes,
             sorted by key; slots past counts[i] hold no record.
    counts   (p,) records per chip (they sum to n when overflow == 0).
    overflow records dropped (0 => every record is in the output).
    splitter_keys / splitter_ranks  the p-1 splitters: (p-1, 4) word
             tuples (3 key words, then the global index that breaks ties)
             and their global ranks.
    stats    the splitters' SplitterStats (None on one chip).
    sent     (p, 2) int32 per chip: record slots it put on the wire in the
             all-to-all (the padded buffer, to the other chips) and how
             many of them held records.
    n        number of input records.
    """

    def __init__(self, shards, counts, overflow, splitter_keys,
                 splitter_ranks, stats, sent, n):
        self.shards = shards
        self.counts = counts
        self.overflow = overflow
        self.splitter_keys = splitter_keys
        self.splitter_ranks = splitter_ranks
        self.stats = stats
        self.sent = sent
        self.n = n

    def gather(self) -> np.ndarray:
        """All records in order as one (n, 100) uint8 array (host)."""
        with spans.span(spans.GATHER):
            shards = np.asarray(self.shards)
            counts = np.asarray(self.counts)
            return unpack_records(np.concatenate(
                [shards[i, :, :counts[i]] for i in range(shards.shape[0])],
                axis=1))

    def counters(self) -> dict:
        """The program's counters, read to the host: splitter rounds run,
        and per chip the bytes it put on the wire in the all-to-all and
        the bytes of real records among them (`repro.runtime.spans`
        names the keys)."""
        sent = np.asarray(self.sent).astype(np.int64) * RECORD_BYTES
        rounds = (0 if self.stats is None
                  else int(np.asarray(self.stats.rounds_used)))
        return {spans.ROUNDS_USED: rounds,
                spans.WIRE_BYTES: sent[:, 0].tolist(),
                spans.SENT_BYTES: sent[:, 1].tolist()}


@dataclasses.dataclass
class RecordPlan:
    """What `make_plan` decides for records: nothing to encode (the word
    planes are the sort's domain already), so it checks them and decodes
    the launch's result."""
    spec: SortSpec
    p: int
    n: int

    @classmethod
    def of(cls, x, spec: SortSpec, p: int) -> "RecordPlan":
        if x.ndim != 2 or x.shape[0] != RECORD_WORDS \
                or x.dtype != jnp.uint32:
            raise ValueError(
                f"records are ({RECORD_WORDS}, n) uint32 word planes, got "
                f"{x.shape} {x.dtype}")
        if x.shape[1] == 0:
            raise ValueError("cannot sort no records")
        return cls(spec=spec, p=p, n=int(x.shape[1]))

    def decode(self, raw) -> RecordSortOutput:
        shards, counts, skeys, sranks, overflow, stats = raw
        if stats is None:   # one chip: a local sort, nothing sent
            splitter_stats, sent = None, jnp.zeros((1, 2), jnp.int32)
        else:
            splitter_stats, sent = stats
        return RecordSortOutput(shards, counts, overflow, skeys, sranks,
                                splitter_stats, sent, self.n)


def local_sort_records(x):
    """One chip's record sort: (25, n) planes by their key words."""
    from repro.kernels import dispatch
    return dispatch.sort_planes(x, RECORD_KEY_WORDS)[0]
