"""jit'd k-way merge entry points (DESIGN.md Section 2.5).

merge_sorted_runs   (k, r) equal-capacity sorted rows -> (k*r,) sorted.
merge_flat_runs     contiguous equal-length sorted runs in a flat array.
merge_ragged_runs   runs at *traced* offsets/lengths inside a flat buffer,
                    with an in-kernel full-sort fallback when a run exceeds
                    the static slot bound.
gather_runs         ragged runs -> static sentinel-padded (k, slot) buffer.
*_batched           the same contracts with a leading request-batch axis,
                    one kernel launch per cascade pass for the whole batch
                    (rows laid end to end; DESIGN.md Section 6.2). The
                    unbatched forms are batches of one.

All merges are exact: given the documented layout (sorted runs, sentinel
filled slack) and the core key contract (NaN-free, non-sentinel keys — a
float NaN propagates through both min/max lanes of a comparator network;
see repro.kernels.__init__) the output is bit-identical to `jnp.sort` over
the same entries. k runs merge in log(k) stages of the bitonic network
(odd runs reversed once, in XLA, so that every pair is bitonic): the
stages that fit the VMEM budget in one kernel pass, each larger stage as
HBM-resident strided passes plus a VMEM tail, so the cascade never falls
back to an XLA sort. The win is kernel residency and
full-width VPU compare-exchanges per pass — not comparator-count
asymptotics: a bitonic merge tree is O(n log k log n) compares.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.common import hi_sentinel, pow2_ceil
from repro.kernels import from_kernel_keys, to_kernel_keys
from repro.kernels import interpret_default as _interpret
from repro.kernels.bitonic_sort import kernel as BK
from repro.kernels.bitonic_sort import ops as bops
from repro.kernels.merge import kernel as MK
from repro.runtime import spans


def merge_cascade(x, run: int, *, vmem_block: int, row_len: int,
                  interpret: bool, family: str):
    """Pairwise-merge tree over the flat array x, whose rows hold `row_len`
    keys (a power of two): each row a sequence of sorted runs of length
    `run` in alternating directions (run j ascending iff j is even) -> each
    row one ascending run. Also the tail of `bitonic_sort.ops.local_sort`
    — one cascade implementation, whatever produced the runs.

    All stages that fit one `vmem_block` run in a single VMEM pass; each
    larger stage is one `merge_level` (strided HBM steps + a VMEM tail).
    `family` names the kernel launches (repro.runtime.spans).
    """
    blk = min(vmem_block, row_len)
    if run < blk:
        x = BK.network_pass(x, block=blk, steps=BK.stages(2 * run, blk),
                            row_len=row_len, interpret=interpret,
                            family=family)
        run = blk
    while run < row_len:
        x = MK.merge_level(x, run, vmem_block=blk, row_len=row_len,
                           interpret=interpret, family=family)
        run *= 2
    return x


def _merge_rows(runs, vmem_block, interpret):
    """(B, k, r) ascending rows -> (B, k*r) ascending."""
    b, k, r = runs.shape
    if k == 1:
        return runs.reshape(b, r)
    dtype = runs.dtype
    runs = to_kernel_keys(runs)
    sent = hi_sentinel(runs.dtype)
    r2 = pow2_ceil(r)
    k2 = max(pow2_ceil(k), bops.MIN_ROW // r2)
    runs = jnp.concatenate(
        [runs, jnp.full((b, k, r2 - r), sent, runs.dtype)], axis=2)
    runs = jnp.concatenate(
        [runs, jnp.full((b, k2 - k, r2), sent, runs.dtype)], axis=1)
    # odd runs reversed, so that every pair of runs is bitonic
    odd = jax.lax.broadcasted_iota(jnp.int32, runs.shape, 1) % 2 == 1
    flat = jnp.where(odd, jnp.flip(runs, 2), runs).reshape(-1)
    # Left free to fuse the flip into the cascade's first strided pass,
    # XLA:TPU took over five minutes to compile a (4, 2^26) merge; apart,
    # about two seconds.
    flat = jax.lax.optimization_barrier(flat)
    out = merge_cascade(flat, r2, vmem_block=vmem_block, row_len=k2 * r2,
                        interpret=interpret, family=spans.MERGE_FAMILY)
    return from_kernel_keys(out.reshape(b, k2 * r2)[:, :k * r], dtype)


@functools.partial(jax.jit, static_argnames=("vmem_block", "interpret"))
def merge_sorted_runs(runs, vmem_block: int | None = None,
                      interpret: bool | None = None):
    """Merge the k sorted rows of a (k, r) array into one sorted (k*r,) run.

    Rows may carry sentinel-padded tails (sentinels are ordinary largest
    keys). k and r need not be powers of two: rows/columns are sentinel
    padded up to the next power internally and the pad is sliced back off —
    sentinels sort to the global tail, so the slice is exact.
    """
    return merge_sorted_runs_batched(runs[None], vmem_block=vmem_block,
                                     interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("vmem_block", "interpret"))
def merge_sorted_runs_batched(runs, vmem_block: int | None = None,
                              interpret: bool | None = None):
    """Per-request k-way merge: (B, k, r) sorted rows -> (B, k*r) sorted.

    The batched form of `merge_sorted_runs` — one cascade over all B
    requests per pass instead of B separate cascades.
    """
    interpret = _interpret() if interpret is None else interpret
    vmem_block = bops.MAX_RUN if vmem_block is None else vmem_block
    b, k, r = runs.shape
    if k * r == 0:
        return jnp.zeros((b, k * r), runs.dtype)
    return _merge_rows(runs, vmem_block, interpret)


@functools.partial(jax.jit, static_argnames=("run", "vmem_block", "interpret"))
def merge_flat_runs(x, run: int, vmem_block: int | None = None,
                    interpret: bool | None = None):
    """Merge back-to-back sorted runs of equal static length `run`."""
    n = x.shape[0]
    assert n % run == 0, (n, run)
    return merge_sorted_runs(x.reshape(n // run, run), vmem_block=vmem_block,
                             interpret=interpret)


def gather_runs(buf, starts, counts, slot: int):
    """Extract k runs at traced offsets into a sentinel-padded (k, slot)
    buffer. Slots past counts[i] hold the sentinel; entries of a run beyond
    `slot` are NOT represented (callers detect via counts > slot).

    Each run is one contiguous window, so it is copied as one: a
    `dynamic_slice` of `slot` keys out of `buf` padded by `slot`
    sentinels, which no start in [0, len(buf)] can push past the end. An
    element-indexed gather of the same windows costs XLA:TPU a tile per
    key. Requires 0 <= starts[i] and starts[i] + counts[i] <= len(buf).

    A buf of (..., n) — records as word planes — cuts the same windows
    of every row at once: (..., k, slot). Not under `jax.vmap`, whose
    `dynamic_slice` becomes a gather."""
    sent = hi_sentinel(buf.dtype)
    n, lead = buf.shape[-1], buf.shape[:-1]
    padded = jnp.concatenate(
        [buf, jnp.full(lead + (slot,), sent, buf.dtype)], axis=-1)
    starts = jnp.clip(jnp.asarray(starts, jnp.int32), 0, n)
    vals = jnp.stack(
        [jax.lax.dynamic_slice(padded, (0,) * len(lead) + (starts[i],),
                               lead + (slot,))
         for i in range(starts.shape[0])], axis=-2)
    pos = jnp.arange(slot, dtype=jnp.int32)[None, :]
    valid = pos < jnp.asarray(counts, jnp.int32)[:, None]
    return jnp.where(valid, vals, sent)


@functools.partial(jax.jit, static_argnames=("slot", "vmem_block", "interpret"))
def merge_ragged_runs(buf, starts, counts, slot: int | None = None,
                      vmem_block: int | None = None,
                      interpret: bool | None = None):
    """Sort a flat buffer holding k sorted runs at traced offsets.

    Layout contract: buf[starts[i] : starts[i]+counts[i]] is sorted
    ascending for each i, runs do not overlap, and every other slot holds
    the dtype's hi sentinel. The result is then bit-identical to
    `jnp.sort(buf)`.

    `slot` is the static per-run capacity of the merge tree (memory is
    k*slot). Runs are bounded by traced counts, so a run *can* exceed a
    tight slot; that case is detected on device and routed to the bitonic
    full-sort fallback via lax.cond — still exact, still kernel-resident.
    slot=None uses the provably sufficient bound (the whole buffer).
    """
    return merge_ragged_runs_batched(
        buf[None], jnp.asarray(starts)[None], jnp.asarray(counts)[None],
        slot=slot, vmem_block=vmem_block, interpret=interpret)[0]


def cap_to(merged, cap: int):
    """Slice/pad the trailing axis of sorted runs to a static capacity
    (sentinel-filled tail)."""
    n = merged.shape[-1]
    if n >= cap:
        return merged[..., :cap]
    pad = jnp.full(merged.shape[:-1] + (cap - n,), hi_sentinel(merged.dtype),
                   merged.dtype)
    return jnp.concatenate([merged, pad], axis=-1)


@functools.partial(jax.jit, static_argnames=("slot", "vmem_block", "interpret"))
def merge_ragged_runs_batched(buf, starts, counts, slot: int | None = None,
                              vmem_block: int | None = None,
                              interpret: bool | None = None):
    """Batched `merge_ragged_runs`: buf (B, cap) flat buffers each holding k
    sorted runs at traced offsets starts/counts (B, k). The spill fallback
    is batch-wide (lax.cond over any row spilling -> one batched full sort),
    keeping the whole batch on a single code path per launch.
    """
    interpret = _interpret() if interpret is None else interpret
    b, cap = buf.shape
    slot = pow2_ceil(cap if slot is None else min(slot, cap))

    def merge_path(bufs):
        runs = jax.vmap(gather_runs, in_axes=(0, 0, 0, None))(
            bufs, starts, counts, slot)
        merged = merge_sorted_runs_batched(runs, vmem_block=vmem_block,
                                           interpret=interpret)
        return cap_to(merged, cap)

    if slot >= cap:
        return merge_path(buf)
    spill = jnp.any(jnp.asarray(counts, jnp.int32) > slot)
    return jax.lax.cond(
        spill,
        lambda bu: bops.local_sort_batched(bu, interpret=interpret),
        merge_path, buf)


def concat_runs(runs, counts, size: int):
    """Lay k runs end to end: runs (..., k, slot) whose first counts[i]
    entries along the last axis are valid -> (..., size), run 0's entries
    first, then run 1's, ... Each run is one `dynamic_update_slice` of its
    whole slot, in order, so the next run overwrites a run's tail; past
    the last run's entries the buffer holds its tail, then the hi
    sentinel. Entries past `size` are cut off (callers count them as
    truncated)."""
    k, slot = runs.shape[-2], runs.shape[-1]
    counts = jnp.asarray(counts, jnp.int32)
    offsets = jnp.minimum(jnp.cumsum(counts) - counts, size)
    buf = jnp.full(runs.shape[:-2] + (size + slot,), hi_sentinel(runs.dtype),
                   runs.dtype)
    lead = (0,) * (runs.ndim - 2)
    for i in range(k):
        buf = jax.lax.dynamic_update_slice(buf, runs[..., i, :],
                                           lead + (offsets[i],))
    return buf[..., :size]
