"""Key redistribution (the data-exchange phase, paper Section 3.1 step 3).

Four strategies, selected by `ExchangeConfig.strategy` (DESIGN.md Section 2):

  dense     capacity-padded jax.lax.all_to_all. One fused all-to-all per sort —
            the TPU-idiomatic MPI_Alltoallv equivalent for well-spread inputs.
            Per-(src,dst) capacity is static; overflowing keys are dropped AND
            counted (psum), so callers can detect and re-run with a larger
            factor. CPU-compilable => used by the multi-pod dry-run.
  dense_spill  the dense channel plus an exact spill channel: keys beyond a
            pair's capacity are compacted into a small side buffer,
            all_gather'ed, and each destination picks its key-range windows
            — so send-side overflow costs extra bandwidth instead of
            dropped keys. This is the `SortSpec(on_overflow="spill")`
            trace; CPU-compilable (no ragged opcode needed), overflow can
            only come from receive-side truncation.
  ragged    jax.lax.ragged_all_to_all — exact alltoallv. XLA:TPU only (XLA:CPU
            lacks the opcode), so it is the production path on hardware but
            excluded from CPU tests/dry-run.
  allgather exact and simple: gather everything, keep own range. O(N) per
            shard; for tests, tiny meshes, and final intra-stage sorts.

All strategies return a sentinel-padded, locally sorted output shard of static
shape (out_cap,) plus the valid-key count. HSS's globally balanced splitting
guarantees valid <= (1+eps) * N/p, which is what makes a static out_cap sound
(this is the paper's epsilon doing real work on TPU: it bounds the buffers).

Every strategy receives p *already sorted* runs, so the post-exchange merge
is a k-way merge (repro.kernels.dispatch.merge_runs / merge_ragged —
log(p) kernel-resident streaming passes), not a from-scratch re-sort:
dense hands the merge p
runs of pair_cap, ragged hands it runs at the received offsets, allgather
hands it the kept window of each source shard. `ExchangeConfig.kernel_policy`
selects the merge backend (Pallas kernels vs the XLA oracle).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.common import hi_sentinel, round_up

#: Collectives each exchange strategy issues per (single-request) call —
#: the static wire contract the analysis lint proves against the traced
#: program. dense: payload + counts all_to_all, overflow psum before and
#: truncation psum after; dense_spill: the dense channel (its pre-psum
#: fused away by construction) + spill payload/count all_gathers + one
#: truncation psum; allgather: payload + counts all_gather + truncation
#: psum; ragged: counts + offsets all_to_all around one ragged_all_to_all
#: (only XLA:TPU compiles it; the lint traces it anywhere). The batched
#: variants fuse the same collectives across B
#: for dense/allgather (B-invariant, also proven by the lint);
#: dense_spill_batched and ragged_batched run per-row loops (documented
#: above) and are exempt from batch invariance.
EXCHANGE_COLLECTIVES = {
    "dense": {"all_to_all": 2, "all_gather": 0, "psum": 2},
    "dense_spill": {"all_to_all": 2, "all_gather": 2, "psum": 1},
    "allgather": {"all_to_all": 0, "all_gather": 2, "psum": 1},
    "ragged": {"all_to_all": 2, "all_gather": 0, "ragged_all_to_all": 1,
               "psum": 0},
}

#: Batched exchange strategies whose collective count is B-invariant.
BATCH_FUSED_STRATEGIES = ("dense", "allgather")


def _kernels():
    """Deferred: repro.kernels modules import repro.core.common, whose
    package init imports this module — resolve at trace time instead."""
    from repro.kernels import dispatch
    from repro.kernels.merge.ops import gather_runs
    return dispatch, gather_runs


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    strategy: str = "dense"      # dense | dense_spill | ragged | allgather
    pair_factor: float = 3.0      # dense: per-(src,dst) capacity = factor*n/p
    out_slack: float = 1.0        # extra slack on the (1+eps) output capacity
    capacity_scale: float = 1.0   # overflow-retry escalation multiplier
    kernel_policy: str = "auto"   # post-exchange merge backend (dispatch)
    out_extra: int = 0            # additive output headroom (semisort lights)

    def pair_cap(self, n_local: int, p: int) -> int:
        # The chaos clamp (fault injection) applies to the BASE capacity;
        # `capacity_scale` multiplies after it, so the overflow-retry
        # escalation can out-grow an injected clamp — which is exactly the
        # recovery path the clamp exists to exercise.
        from repro.runtime import chaos
        base = chaos.clamp_pair_cap(max(8, int(self.pair_factor * n_local / p)))
        return min(n_local, round_up(max(1, int(base * self.capacity_scale)), 8))

    def out_cap(self, n_local: int, p: int, eps: float) -> int:
        # out_extra is additive headroom on top of the multiplicative slack:
        # the semisort light path uses it for classes just under the heavy
        # detection threshold, which cannot be split across splitters.
        return round_up(
            int((1.0 + eps) * self.out_slack * self.capacity_scale * n_local)
            + self.out_extra + 8, 8)

    def ragged_slot(self, n_local: int, p: int, eps: float) -> int:
        """Static per-run capacity of the ragged merge tree: double the
        balanced per-pair load. Runs that exceed it (splitting violated its
        eps guarantee) divert to the in-kernel full-sort fallback."""
        return min(n_local, max(16, int(2.0 * (1.0 + eps) * n_local / p)))


def _cap_to(merged, out_cap):
    """Slice/pad merged runs (last axis) to the static output capacity."""
    from repro.kernels.merge.ops import cap_to
    return cap_to(merged, out_cap)


def destination_slices(local_sorted: jax.Array, splitter_keys: jax.Array,
                       n_valid=None):
    """Contiguous [start, end) slice of the local sorted shard per destination.

    n_valid (traced ok) excludes a sentinel-padded tail from the last slice.
    """
    n = local_sorted.shape[0]
    n_valid = jnp.asarray(n if n_valid is None else n_valid, jnp.int32)
    b = jnp.searchsorted(local_sorted, splitter_keys, side="left").astype(jnp.int32)
    b = jnp.minimum(b, n_valid)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), b])
    ends = jnp.concatenate([b, n_valid[None]])
    return starts, ends - starts


def exchange_dense(local_sorted, splitter_keys, *, axis_name, p, cfg, eps,
                   n_valid=None):
    n = local_sorted.shape[0]
    cap = cfg.pair_cap(n, p)
    out_cap = cfg.out_cap(n, p, eps)
    sent_hi = hi_sentinel(local_sorted.dtype)

    starts, counts = destination_slices(local_sorted, splitter_keys, n_valid)
    sent_counts = jnp.minimum(counts, cap)
    overflow = jax.lax.psum(jnp.sum(counts - sent_counts), axis_name)

    idx = starts[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < sent_counts[:, None]
    buf = jnp.where(valid, local_sorted[jnp.clip(idx, 0, n - 1)], sent_hi)

    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    recv_counts = jax.lax.all_to_all(
        sent_counts.reshape(p, 1), axis_name, 0, 0, tiled=False).reshape(p)
    # p sorted sentinel-tailed runs of cap keys -> one k-way merge.
    dispatch, _ = _kernels()
    merged = dispatch.merge_runs(recv, policy=cfg.kernel_policy)
    out = _cap_to(merged, out_cap)
    n_recv = jnp.sum(recv_counts)
    # Receive-side truncation (only possible when the splitting violated its
    # eps guarantee, e.g. an undersized sample-sort sample) is overflow too.
    trunc = jnp.maximum(n_recv - out_cap, 0)
    overflow = overflow + jax.lax.psum(trunc, axis_name)
    return out, n_recv - trunc, overflow


def exchange_dense_spill(local_sorted, splitter_keys, *, axis_name, p, cfg,
                         eps, n_valid=None):
    """Dense all-to-all plus an exact spill channel for over-capacity keys.

    The dense channel runs exactly as `exchange_dense` (same pair_cap, same
    fused all_to_all). Keys a source would have dropped — positions past
    their destination slice's capacity — are instead compacted into a
    sentinel-padded (n_local,) spill buffer and all_gather'ed; each
    destination picks its key-range window out of every source's spill run
    (the same two-binary-searches-per-run trick as `exchange_allgather`,
    restricted to the spilled keys) and merges those windows together with
    the dense runs. Spilled keys land on the same destination the dense
    slices would have sent them to (windows are value-range based and
    destination slices are value-contiguous), so the result is
    bit-identical to an uncapped dense exchange.

    Cost: one extra all_gather of the spill buffer — O(p * n_local) worst
    case but proportional to actual spill in practice (the buffer is
    sentinel-compacted; with zero spill the gather moves sentinels and the
    merge drops them). Overflow can only be receive-side truncation
    (out_cap), which the (1+eps) guarantee rules out for converged
    splitters — so this is the capacity-overflow-proof CPU-compilable
    path behind `SortSpec(on_overflow="spill")`.
    """
    n = local_sorted.shape[0]
    cap = cfg.pair_cap(n, p)
    out_cap = cfg.out_cap(n, p, eps)
    sent_hi = hi_sentinel(local_sorted.dtype)
    me = jax.lax.axis_index(axis_name)
    nv = jnp.asarray(n if n_valid is None else n_valid, jnp.int32)

    starts, counts = destination_slices(local_sorted, splitter_keys, n_valid)
    sent_counts = jnp.minimum(counts, cap)

    # -- dense channel (identical to exchange_dense)
    idx = starts[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < sent_counts[:, None]
    buf = jnp.where(valid, local_sorted[jnp.clip(idx, 0, n - 1)], sent_hi)
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    recv_counts = jax.lax.all_to_all(
        sent_counts.reshape(p, 1), axis_name, 0, 0, tiled=False).reshape(p)

    # -- spill channel: position i spills iff its offset within its
    # destination slice is past that pair's capacity
    dispatch, gather_runs = _kernels()
    pos = jnp.arange(n, dtype=jnp.int32)
    dest = jnp.searchsorted(starts[1:], pos, side="right").astype(jnp.int32)
    offset = pos - starts[dest]
    spilled = (offset >= sent_counts[dest]) & (pos < nv)
    n_spill = jnp.sum(spilled.astype(jnp.int32))
    spill = dispatch.local_sort(   # compact: spilled keys stay sorted
        jnp.where(spilled, local_sorted, sent_hi), policy=cfg.kernel_policy)
    every = jax.lax.all_gather(spill, axis_name, tiled=True)     # (p*n,)
    nv_sp = jax.lax.all_gather(n_spill[None], axis_name, tiled=True)  # (p,)
    rows = every.reshape(p, n)
    lo = splitter_keys[jnp.maximum(me - 1, 0)]
    hi = splitter_keys[jnp.minimum(me, p - 2)]
    a = jax.vmap(lambda r: jnp.searchsorted(r, lo, side="left"))(rows)
    b = jax.vmap(lambda r: jnp.searchsorted(r, hi, side="left"))(rows)
    a = jnp.where(me > 0, a.astype(jnp.int32), 0)
    b = jnp.where(me < p - 1, b.astype(jnp.int32), n)
    s_ends = jnp.minimum(b, nv_sp)
    s_starts = jnp.minimum(a, s_ends)
    s_counts = s_ends - s_starts
    flat_starts = jnp.arange(p, dtype=jnp.int32) * n + s_starts
    spill_runs = gather_runs(every, flat_starts, s_counts, n)    # (p, n)

    # -- merge both channels: p dense runs + p spill-window runs
    if cap < n:
        dense_rows = jnp.concatenate(
            [recv, jnp.full((p, n - cap), sent_hi, recv.dtype)], axis=1)
    else:
        dense_rows = recv
    merged = dispatch.merge_runs(
        jnp.concatenate([dense_rows, spill_runs], axis=0),
        policy=cfg.kernel_policy)
    out = _cap_to(merged, out_cap)
    n_recv = jnp.sum(recv_counts) + jnp.sum(s_counts)
    trunc = jnp.maximum(n_recv - out_cap, 0)
    return out, n_recv - trunc, jax.lax.psum(trunc, axis_name)


def exchange_allgather(local_sorted, splitter_keys, *, axis_name, p, cfg, eps,
                       n_valid=None):
    n = local_sorted.shape[0]
    out_cap = cfg.out_cap(n, p, eps)
    me = jax.lax.axis_index(axis_name)

    everything = jax.lax.all_gather(local_sorted, axis_name, tiled=True)
    nv_local = jnp.asarray(n if n_valid is None else n_valid, jnp.int32)
    nv = jax.lax.all_gather(nv_local[None], axis_name, tiled=True)   # (p,)
    rows = everything.reshape(p, n)
    # My key range [lo, hi) is a contiguous window of each (sorted) source
    # run: two vmapped binary searches per run, not an O(p*n) mask.
    lo = splitter_keys[jnp.maximum(me - 1, 0)]
    hi = splitter_keys[jnp.minimum(me, p - 2)]
    a = jax.vmap(lambda r: jnp.searchsorted(r, lo, side="left"))(rows)
    b = jax.vmap(lambda r: jnp.searchsorted(r, hi, side="left"))(rows)
    a = jnp.where(me > 0, a.astype(jnp.int32), 0)
    b = jnp.where(me < p - 1, b.astype(jnp.int32), n)
    ends = jnp.minimum(b, nv)
    starts = jnp.minimum(a, ends)
    counts = ends - starts
    n_out = jnp.sum(counts)

    dispatch, gather_runs = _kernels()
    flat_starts = jnp.arange(p, dtype=jnp.int32) * n + starts
    # slot = n bounds every window exactly (a source can contribute at most
    # its whole run); merge_runs pads the row length internally as needed.
    runs = gather_runs(everything, flat_starts, counts, n)
    merged = dispatch.merge_runs(runs, policy=cfg.kernel_policy)
    vals = _cap_to(merged, out_cap)
    trunc = jnp.maximum(n_out - out_cap, 0)
    return vals, n_out - trunc, jax.lax.psum(trunc, axis_name)


#: Keys per row of the ragged exchange's send and receive buffers.
RAGGED_ROW = 128


def _exclusive_cumsum(x):
    return jnp.cumsum(x) - x


def row_aligned_chunks(local_sorted, starts, counts, rows: int):
    """Restage the destination slices of a sorted shard so that each starts
    on a row of RAGGED_ROW keys: returns the (rows, RAGGED_ROW) staging
    buffer (slice d from row `row_starts[d]`, sentinel-filled up to the
    next row) and each slice's row offset and row count."""
    n = local_sorted.shape[0]
    row_counts = -(-counts // RAGGED_ROW)
    row_starts = _exclusive_cumsum(row_counts)
    pos = jnp.arange(rows * RAGGED_ROW, dtype=jnp.int32)
    src = jnp.full(pos.shape, -1, jnp.int32)
    for d in range(starts.shape[0]):
        first = row_starts[d] * RAGGED_ROW
        mine = (pos >= first) & (pos < first + counts[d])
        src = jnp.where(mine, pos - first + starts[d], src)
    staged = jnp.where(src >= 0, local_sorted[jnp.clip(src, 0, n - 1)],
                       hi_sentinel(local_sorted.dtype))
    return staged.reshape(rows, RAGGED_ROW), row_starts, row_counts


def exchange_ragged(local_sorted, splitter_keys, *, axis_name, p, cfg, eps,
                    n_valid=None):
    """Exact alltoallv via jax.lax.ragged_all_to_all. TPU-only (see module doc).

    XLA:TPU lays a 1-D ragged operand out as one key per 128-lane row, so
    keys travel in rows of RAGGED_ROW: each destination slice is restaged
    to start on a row, and each received run starts on one too."""
    n = local_sorted.shape[0]
    out_cap = cfg.out_cap(n, p, eps)
    sent_hi = hi_sentinel(local_sorted.dtype)

    starts, counts = destination_slices(local_sorted, splitter_keys, n_valid)
    staged, send_starts, send_rows = row_aligned_chunks(
        local_sorted, starts, counts, -(-n // RAGGED_ROW) + p)
    # recv_counts[s] = how many keys I receive from source s.
    recv_counts = jax.lax.all_to_all(
        counts.reshape(p, 1), axis_name, 0, 0, tiled=False).reshape(p)
    recv_rows = -(-recv_counts // RAGGED_ROW)
    recv_starts = _exclusive_cumsum(recv_rows)
    # send_offsets[d] = row within destination d's buffer of my chunk.
    send_offsets = jax.lax.all_to_all(
        recv_starts.reshape(p, 1), axis_name, 0, 0, tiled=False).reshape(p)
    out = jnp.full((-(-out_cap // RAGGED_ROW) + p, RAGGED_ROW), sent_hi,
                   local_sorted.dtype)
    out = jax.lax.ragged_all_to_all(
        staged, out, send_starts, send_rows, send_offsets, recv_rows,
        axis_name=axis_name)
    n_valid = jnp.sum(recv_counts)
    # p sorted runs at known (traced) offsets: k-way merge, with the
    # in-kernel full-sort fallback if a run overflows the static slot.
    dispatch, _ = _kernels()
    out = dispatch.merge_ragged(
        out.reshape(-1), recv_starts * RAGGED_ROW, recv_counts,
        policy=cfg.kernel_policy, slot=cfg.ragged_slot(n, p, eps))
    return out[:out_cap], n_valid, jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# Batched exchange: B independent requests, one collective per phase.
#
# The batched sort engine (repro.sort.api.sort_batched) runs B requests
# through a single shard_map launch; the exchange is where the per-request
# collectives would otherwise multiply. Each strategy's batched variant
# moves the whole (B, ...) payload in ONE collective:
#   dense      one all_to_all over a (p, B, cap) buffer (+ one for counts);
#   allgather  one all_gather of the (B, n_local) shard;
#   ragged     per-request ragged_all_to_all loop (TPU-only; the opcode
#              takes one chunk per peer, so fusing B requests would need a
#              repacked staging buffer — future work, documented in
#              DESIGN.md Section 6).
# Per-request results are bit-identical to the unbatched strategy run on
# that request's row.
# ---------------------------------------------------------------------------


def _rows_valid(n_valid, b, n):
    """Normalize the batched n_valid parameter to a (B,) vector: None means
    every slot is real; a scalar applies to every request; (B,) per-request
    counts pass through."""
    if n_valid is None:
        return jnp.full((b,), n, jnp.int32)
    return jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (b,))


def exchange_dense_batched(local_sorted, splitter_keys, *, axis_name, p, cfg,
                           eps, n_valid=None):
    """Batched capacity-padded all-to-all: local_sorted (B, n_local),
    splitter_keys (B, p-1) -> (out (B, out_cap), n_valid (B,), ovf (B,))."""
    b, n = local_sorted.shape
    cap = cfg.pair_cap(n, p)
    out_cap = cfg.out_cap(n, p, eps)
    sent_hi = hi_sentinel(local_sorted.dtype)

    starts, counts = jax.vmap(destination_slices)(
        local_sorted, splitter_keys, _rows_valid(n_valid, b, n))  # (B, p)
    sent_counts = jnp.minimum(counts, cap)
    overflow = jax.lax.psum(
        jnp.sum(counts - sent_counts, axis=1), axis_name)  # (B,)

    idx = starts[:, :, None] + jnp.arange(cap, dtype=jnp.int32)[None, None, :]
    valid = (jnp.arange(cap, dtype=jnp.int32)[None, None, :]
             < sent_counts[:, :, None])
    rows = jnp.take_along_axis(local_sorted, jnp.clip(idx, 0, n - 1)
                               .reshape(b, -1), axis=1).reshape(b, p, cap)
    buf = jnp.where(valid, rows, sent_hi)                # (B, p, cap)

    # ONE all_to_all for the whole batch: shard axis leading.
    recv = jax.lax.all_to_all(jnp.swapaxes(buf, 0, 1), axis_name, 0, 0,
                              tiled=False)               # (p, B, cap)
    recv = jnp.swapaxes(recv, 0, 1)                      # (B, p, cap)
    recv_counts = jax.lax.all_to_all(
        sent_counts.T[..., None], axis_name, 0, 0, tiled=False)[..., 0].T

    dispatch, _ = _kernels()
    merged = dispatch.merge_runs_batched(recv, policy=cfg.kernel_policy)
    out = _cap_to(merged, out_cap)
    n_recv = jnp.sum(recv_counts, axis=1)                # (B,)
    trunc = jnp.maximum(n_recv - out_cap, 0)
    overflow = overflow + jax.lax.psum(trunc, axis_name)
    return out, n_recv - trunc, overflow


def exchange_allgather_batched(local_sorted, splitter_keys, *, axis_name, p,
                               cfg, eps, n_valid=None):
    b, n = local_sorted.shape
    out_cap = cfg.out_cap(n, p, eps)
    me = jax.lax.axis_index(axis_name)

    # ONE all_gather of the whole (B, n_local) shard.
    everything = jax.lax.all_gather(local_sorted, axis_name)   # (p, B, n)
    nv = jax.lax.all_gather(_rows_valid(n_valid, b, n), axis_name)  # (p, B)
    lo = splitter_keys[:, jnp.maximum(me - 1, 0)]              # (B,)
    hi = splitter_keys[:, jnp.minimum(me, p - 2)]              # (B,)
    search = jax.vmap(jax.vmap(
        lambda r, q: jnp.searchsorted(r, q, side="left"),
        in_axes=(0, 0)), in_axes=(0, None))
    a = search(everything, lo)                                 # (p, B)
    bq = search(everything, hi)
    a = jnp.where(me > 0, a.astype(jnp.int32), 0)
    bq = jnp.where(me < p - 1, bq.astype(jnp.int32), n)
    ends = jnp.minimum(bq, nv)
    starts = jnp.minimum(a, ends)
    counts = ends - starts                                     # (p, B)
    n_out = jnp.sum(counts, axis=0)                            # (B,)

    dispatch, gather_runs = _kernels()
    flat = jnp.swapaxes(everything, 0, 1).reshape(b, p * n)    # (B, p*n)
    flat_starts = (jnp.arange(p, dtype=jnp.int32)[:, None] * n + starts).T
    runs = jax.vmap(gather_runs, in_axes=(0, 0, 0, None))(
        flat, flat_starts, counts.T, n)                        # (B, p, n)
    merged = dispatch.merge_runs_batched(runs, policy=cfg.kernel_policy)
    vals = _cap_to(merged, out_cap)
    trunc = jnp.maximum(n_out - out_cap, 0)
    return vals, n_out - trunc, jax.lax.psum(trunc, axis_name)


def exchange_ragged_batched(local_sorted, splitter_keys, *, axis_name, p,
                            cfg, eps, n_valid=None):
    """Per-request ragged_all_to_all loop (see module note above): still one
    *launch* for the batch, B exact alltoallv collectives inside it."""
    b, n = local_sorted.shape
    rows_valid = _rows_valid(n_valid, b, n)
    outs, nvs, ovfs = [], [], []
    for i in range(b):
        o, nv, ov = exchange_ragged(
            local_sorted[i], splitter_keys[i], axis_name=axis_name, p=p,
            cfg=cfg, eps=eps, n_valid=rows_valid[i])
        outs.append(o), nvs.append(nv), ovfs.append(ov)
    return jnp.stack(outs), jnp.stack(nvs), jnp.stack(ovfs)


def exchange_dense_spill_batched(local_sorted, splitter_keys, *, axis_name,
                                 p, cfg, eps, n_valid=None):
    """Per-request dense_spill loop: still ONE launch for the batch, B x
    the collectives of a single request inside it (the spill channel's
    per-row windows do not batch-fuse yet — same status as the ragged
    strategy; DESIGN.md Section 6 tracks the fusion)."""
    b, n = local_sorted.shape
    rows_valid = _rows_valid(n_valid, b, n)
    outs, nvs, ovfs = [], [], []
    for i in range(b):
        o, nv, ov = exchange_dense_spill(
            local_sorted[i], splitter_keys[i], axis_name=axis_name, p=p,
            cfg=cfg, eps=eps, n_valid=rows_valid[i])
        outs.append(o), nvs.append(nv), ovfs.append(ov)
    return jnp.stack(outs), jnp.stack(nvs), jnp.stack(ovfs)


_STRATEGIES = {
    "dense": exchange_dense,
    "dense_spill": exchange_dense_spill,
    "ragged": exchange_ragged,
    "allgather": exchange_allgather,
}

_STRATEGIES_BATCHED = {
    "dense": exchange_dense_batched,
    "dense_spill": exchange_dense_spill_batched,
    "ragged": exchange_ragged_batched,
    "allgather": exchange_allgather_batched,
}


def exchange(local_sorted, splitter_keys, *, axis_name, p,
             cfg: ExchangeConfig | None = None, eps: float = 0.05,
             n_valid=None):
    cfg = cfg or ExchangeConfig()
    try:
        fn = _STRATEGIES[cfg.strategy]
    except KeyError:
        raise ValueError(f"unknown exchange strategy {cfg.strategy!r}") from None
    return fn(local_sorted, splitter_keys, axis_name=axis_name, p=p,
              cfg=cfg, eps=eps, n_valid=n_valid)


def exchange_batched(local_sorted, splitter_keys, *, axis_name, p,
                     cfg: ExchangeConfig | None = None, eps: float = 0.05,
                     n_valid=None):
    """Redistribute B requests at once: local_sorted (B, n_local),
    splitter_keys (B, p-1) -> (out (B, out_cap), n_valid (B,), ovf (B,)).
    The `n_valid` parameter may be None (all slots real), a scalar shared
    by every request, or a per-request (B,) vector."""
    cfg = cfg or ExchangeConfig()
    try:
        fn = _STRATEGIES_BATCHED[cfg.strategy]
    except KeyError:
        raise ValueError(f"unknown exchange strategy {cfg.strategy!r}") from None
    return fn(local_sorted, splitter_keys, axis_name=axis_name, p=p,
              cfg=cfg, eps=eps, n_valid=n_valid)
