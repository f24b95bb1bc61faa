"""repro.sort — the public distributed-sorting API (DESIGN.md Section 3).

One `sort()`/`argsort()`/`sort_kv()` surface over every partitioning
strategy in the repo, configured by a single `SortSpec`:

    from repro.sort import SortSpec, sort
    out = sort(x, SortSpec(algorithm="hss", eps=0.05))
    np_sorted = out.gather()

Algorithms (see repro.sort.partitioners): "hss" (the paper), the
"sample_random"/"sample_regular" baselines, "ams", and "multistage"
(two-stage HSS over a nested mesh). New strategies plug in via
`register_partitioner`. The shared host driver lives in repro.sort.driver;
dtype/duplicate adapters in repro.sort.adapters; device-level dispatch
helpers (MoE) in repro.sort.grouping.

Grouping workloads (DESIGN.md Section 10): `semisort(keys)` makes equal
keys contiguous without paying for a total order (heavy hitters bypass the
exchange entirely), `groupby_aggregate(keys, values, op=...)` aggregates
per distinct key, and `top_k(keys, k)` prunes below-threshold keys on each
shard BEFORE any exchange — see repro.sort.semisort.

Batched serving: `sort_batched(xs)` sorts B independent requests in ONE
shard_map launch with batch-fused collectives and a compiled-executable
cache (`exec_cache`) keyed by shape bucket — see DESIGN.md Section 6:

    outs = sort_batched(xs_2d)       # (B, n) -> BatchedSortOutput
    outs = sort_batched([a, b, c])   # length-bucketed list -> per-request

Records (the Sort Benchmark's 100-byte format): `sort_records(words)`
sorts (25, n) uint32 word planes (`pack_records` makes them from (n, 100)
uint8) by their 10-byte keys, the payload moved on the device.

Verified mode (DESIGN.md Section 9): `SortSpec(verify="cheap")` fuses a
device-side postcondition audit (multiset fingerprint + sortedness +
boundary/range + count conservation) into the launch; failures surface as
typed `VerificationError`s or auto-recover per `on_verify_failure`, and
`SortSpec(imbalance_slo=...)` enforces the paper's (1+eps) partition
quality at runtime.

The legacy per-algorithm entry points (`repro.core.hss_sort` et al.) remain
as thin shims over the same driver.
"""
from repro.sort.adapters import (BatchedSortOutput, RecordSortOutput,
                                 SortOutput, pack_records, unpack_records)
from repro.sort.api import (
    RecoveryStats, argsort, bucket_key, gather, gather_perm_checked, sort,
    sort_batched, sort_kv, sort_records, spec_fingerprint)
from repro.sort.driver import exec_cache
from repro.sort.semisort import (
    GROUPBY_OPS, BatchedSemisortOutput, SemisortOutput, groupby_aggregate,
    semisort, semisort_batched, top_k, top_k_batched)
from repro.sort.partitioners import (
    Partitioner, ShardCtx, available_algorithms, get_partitioner,
    register_partitioner)
from repro.sort.spec import (ALGORITHMS, ON_OVERFLOW, ON_VERIFY_FAILURE,
                             VERIFY, SortSpec)
from repro.sort.verify import (AuditReport, BatchVerificationError,
                               ImbalanceError, VerificationError)

__all__ = [
    "ALGORITHMS", "AuditReport", "BatchVerificationError",
    "BatchedSemisortOutput", "BatchedSortOutput", "GROUPBY_OPS",
    "ImbalanceError", "ON_OVERFLOW", "ON_VERIFY_FAILURE", "Partitioner",
    "RecordSortOutput", "RecoveryStats", "SemisortOutput", "ShardCtx", "SortOutput", "SortSpec",
    "VERIFY", "VerificationError", "argsort", "available_algorithms",
    "bucket_key", "exec_cache", "gather", "gather_perm_checked",
    "get_partitioner", "groupby_aggregate", "pack_records",
    "register_partitioner", "semisort", "semisort_batched", "sort",
    "sort_batched", "sort_kv", "sort_records", "spec_fingerprint", "top_k",
    "top_k_batched", "unpack_records",
]
