"""HSS-based global length bucketing (first-class paper integration #2).

Packing variable-length documents into fixed-length training sequences wastes
pad tokens unless similarly sized documents are batched together. Globally
sorting (length, doc_id) keys across the data-loader shards is exactly the
paper's problem: the HSS splitters give every host a near-equal, contiguous
length range with O(p log log p) metadata traffic instead of a full gather.

`bucket_lengths` runs the real distributed sort through the `repro.sort`
front-door; duplicate tagging (lengths duplicate heavily — the AllZeros-ish
regime) and doc-id tracking are the adapter layer's job now, so this module
is just the bucketing policy.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.parallel.compat import make_mesh
from repro.sort import SortSpec, sort


def bucket_lengths(lengths: np.ndarray, n_shards: int, eps: float = 0.05,
                   seed: int = 0, spec: "SortSpec | None" = None):
    """Partition docs into n_shards contiguous-length buckets via HSS.

    Returns (doc_ids_per_shard: list[np.ndarray], counts). Each shard's docs
    have lengths no larger than the next shard's (globally balanced order),
    so per-shard packing sees near-homogeneous lengths.

    Serving note: this call routes through the driver's compiled-executable
    cache (repro.sort.driver.exec_cache) — the mesh fingerprint in the
    cache key is structural, so repeated calls with the same queue size and
    shard count (the steady state of `launch.serve.serve_bucketed`) reuse
    one compiled program instead of re-tracing per request wave. Pass
    `spec` to override the sort configuration; mesh/stability are set here.
    """
    import dataclasses
    import jax
    if n_shards > len(jax.devices()):
        raise ValueError(f"n_shards={n_shards} > {len(jax.devices())} devices")
    mesh = make_mesh((n_shards,), ("sort",),
                     devices=jax.devices()[:n_shards])
    spec = dataclasses.replace(
        spec or SortSpec(algorithm="hss", eps=eps, exchange="allgather"),
        seed=seed, mesh=mesh, stable=True)
    out = sort(jnp.asarray(lengths), spec)
    counts = np.asarray(out.counts)
    indices = np.asarray(out.indices)
    ids = [indices[i, :counts[i]] for i in range(n_shards)]
    return ids, counts


def pack_documents(doc_ids: np.ndarray, lengths: np.ndarray, seq_len: int):
    """First-fit packing of (already length-sorted) docs into sequences.

    Returns list of lists of doc ids; padding fraction is the bench metric.
    """
    seqs, cur, cur_len = [], [], 0
    for d in doc_ids:
        ln = int(lengths[d])
        if cur_len + ln > seq_len and cur:
            seqs.append(cur)
            cur, cur_len = [], 0
        cur.append(int(d))
        cur_len += ln
    if cur:
        seqs.append(cur)
    return seqs


def padding_fraction(seqs, lengths, seq_len: int) -> float:
    used = sum(min(sum(int(lengths[d]) for d in s), seq_len) for s in seqs)
    return 1.0 - used / (len(seqs) * seq_len)
