"""Backend/size-aware kernel dispatch (DESIGN.md Section 2.5).

One selection layer over the three sort hot spots — `local_sort`,
`probe_ranks`, and the post-exchange merges (`merge_runs`/`merge_ragged`) —
so CPU/interpret tests and TPU production share a single code path. Every
core pipeline (hss, sample_sort, ams, multistage, and the partitioner
registry) routes its compute through these functions; the *policy* decides
what actually runs:

  "auto"    (default) the Pallas kernels on TPU, the XLA primitives
            elsewhere. TPU is where the kernels pay for themselves; on CPU
            the kernels only exist in interpret mode, which is a parity
            harness, not a performance path.
  "pallas"  always the Pallas kernels; on non-TPU backends they execute in
            interpret mode (kernel body traced to XLA ops) so the exact
            production dataflow is testable anywhere.
  "xla"     always the XLA primitives (`jnp.sort`, `searchsorted`).

All pairs of backends are exact: for any input honoring the layout
contracts — sorted runs where documented, and the core key contract of
NaN-free, non-sentinel keys (see repro.kernels.__init__; the front-door's
float->int bijection guarantees it) — "pallas" and "xla" return
bit-identical arrays, which is what tests/test_merge_kernel.py pins down.

The policy travels as `SortSpec.kernel_policy` through the front-door and
as `HSSConfig.kernel_policy` / `ExchangeConfig.kernel_policy` at the core
layer. Selection happens at trace time (it is a host-side decision), so it
is free inside jit/shard_map.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bitonic_sort import ops as bops
from repro.kernels.histogram import ops as hops
from repro.kernels.histogram import ref as href
from repro.kernels.merge import ops as mops

POLICIES = ("auto", "pallas", "xla")


def resolve_policy(policy: str = "auto", dtype=None) -> str:
    """-> "pallas" | "xla" for the current backend (and key dtype).

    "auto" only selects the kernels for <=32-bit keys: the tagging adapter
    widens packed keys to int64, and Mosaic TPU has no 64-bit vector
    support — those arrays take the XLA path. An explicit "pallas" is
    honored as given (the caller asked for the kernels; parity tests do).
    """
    if policy not in POLICIES:
        raise ValueError(
            f"unknown kernel_policy {policy!r}; available: {POLICIES}")
    if policy != "auto":
        return policy
    if dtype is not None and jnp.dtype(dtype).itemsize > 4:
        return "xla"
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def local_sort_fn(policy: str = "auto"):
    """The policy bound into a `local_sort_fn`-shaped callable — what every
    pipeline passes to the driver / uses as its default local sort."""
    return lambda x: local_sort(x, policy=policy)


def local_sort_batched_fn(policy: str = "auto"):
    """`local_sort_fn`, row-batched: callable over (B, n) key batches."""
    return lambda x: local_sort_batched(x, policy=policy)


# "auto" size ceiling for a full bitonic sort: the network is
# O(n log^2 n) compares and pads to the next power of two, which is the
# right trade at shard scale but not for whole-array sorts (the p==1
# short-circuit); past this, "auto" keeps XLA. Explicit "pallas" is honored.
AUTO_SORT_MAX_N = 1 << 22


def local_sort(x, *, policy: str = "auto", block: int | None = None):
    """Sort a 1-D array (sentinel-padded inputs welcome: sentinels are
    ordinary largest keys and land on the tail)."""
    if policy == "auto" and x.shape[0] > AUTO_SORT_MAX_N:
        policy = "xla"
    if resolve_policy(policy, x.dtype) == "xla":
        return jnp.sort(x)
    return bops.local_sort(x, block=block or bops.DEFAULT_BLOCK)


def local_sort_batched(x, *, policy: str = "auto", block: int | None = None):
    """Sort each row of a (B, n) batch; one kernel launch per network pass
    for the whole batch (batch grid dimension) on the Pallas path, a single
    axis=-1 `jnp.sort` on the XLA path. Bit-identical per row to
    `local_sort` on that row."""
    if policy == "auto" and x.shape[1] > AUTO_SORT_MAX_N:
        policy = "xla"
    if resolve_policy(policy, x.dtype) == "xla":
        return jnp.sort(x, axis=-1)
    return bops.local_sort_batched(x, block=block or bops.DEFAULT_BLOCK)


def probe_ranks(keys, probes, *, policy: str = "auto",
                assume_sorted: bool = False):
    """rank[m] = #{keys < probes[m]} as int32.

    The Pallas histogram kernel *counts* rather than searches, so it does
    not need `keys` sorted — that is what unlocks ranking unsorted shards
    before a local sort completes. The XLA path uses `searchsorted` when
    `assume_sorted` (every splitter pipeline ranks over locally sorted
    shards) and the sort+search oracle otherwise.
    """
    if probes.shape[0] == 0:
        return jnp.zeros((0,), jnp.int32)
    if resolve_policy(policy, keys.dtype) == "xla":
        if assume_sorted:
            return jnp.searchsorted(keys, probes, side="left").astype(jnp.int32)
        return href.probe_ranks_ref(keys, probes)
    return hops.probe_ranks(keys, probes)


def merge_runs(runs, *, policy: str = "auto", vmem_block: int | None = None):
    """Merge the k sorted rows of a (k, r) array -> (k*r,) sorted.

    Bit-identical to `jnp.sort(runs.reshape(-1))`; the Pallas path merges
    in log(k) kernel-resident streaming passes instead of re-sorting (see
    kernels.merge.ops for the honest cost model).
    """
    if resolve_policy(policy, runs.dtype) == "xla":
        return jnp.sort(runs.reshape(-1))
    return mops.merge_sorted_runs(runs, vmem_block=vmem_block)


def merge_ragged(buf, starts, counts, *, policy: str = "auto",
                 slot: int | None = None, vmem_block: int | None = None):
    """Sort a flat buffer holding sorted runs at traced offsets (sentinel
    elsewhere). Bit-identical to `jnp.sort(buf)`; see
    kernels.merge.ops.merge_ragged_runs for the slot/fallback contract."""
    if resolve_policy(policy, buf.dtype) == "xla":
        return jnp.sort(buf)
    return mops.merge_ragged_runs(buf, starts, counts, slot=slot,
                                  vmem_block=vmem_block)


def sort_planes(planes, num_keys: int):
    """Stable sort of records held as planes: planes (words, n) -> (the
    planes sorted by their first `num_keys` words compared as unsigned
    words lexicographically, ties in input order; order (n,) int32, the
    input position of each output record).

    Every sort here has two operands and one key, which XLA:TPU compiles
    in seconds where a sort of many operands or keys takes minutes: the
    key words are sorted least significant first, each pass one stable
    sort of the key words and the positions by one word, all rows at once
    (rows of a 2-D sort are independent); then the payload rows are
    sorted by each record's output position. No permutation is applied
    as a gather. A multi-word Pallas sort is not written yet, so every
    policy takes this path."""
    n = planes.shape[1]
    pos = jnp.arange(n, dtype=jnp.uint32)
    rows = jnp.concatenate([planes[:num_keys], pos[None]])
    for w in reversed(range(num_keys)):
        key = jnp.broadcast_to(rows[w], rows.shape)
        rows = jax.lax.sort((key, rows), dimension=1, num_keys=1,
                            is_stable=True)[1]
    order = rows[num_keys]
    if planes.shape[0] == num_keys:
        return rows[:num_keys], order.astype(jnp.int32)
    dest = jax.lax.sort((order, pos), num_keys=1)[1]
    payload = planes[num_keys:]
    payload = jax.lax.sort(
        (jnp.broadcast_to(dest, payload.shape), payload), dimension=1,
        num_keys=1)[1]
    return (jnp.concatenate([rows[:num_keys], payload]),
            order.astype(jnp.int32))


def merge_records(runs, counts, size: int, num_keys: int):
    """Merge received record runs: runs (words, k, slot) whose first
    counts[i] records are valid -> (words, size) sorted by the first
    `num_keys` words. The runs are laid end to end (`concat_runs`, one
    contiguous copy each) and sorted with their payload (`sort_planes`);
    the slots past the records hold all-ones words, and the sort is
    stable, so they stay behind a record whose key words are all ones."""
    buf = mops.concat_runs(runs, counts, size)
    return sort_planes(buf, num_keys)[0]
