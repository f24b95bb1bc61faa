"""The batched single-launch sort engine (DESIGN.md Section 6).

Pins the three contracts of `repro.sort.sort_batched`:
  * bit-identity: every request's result equals a sequential `sort()` of
    that request with the same spec/seed, across dtypes and partitioners;
  * collective fusion: one all_gather + one psum per splitter round and one
    payload all_to_all for the dense exchange, independent of B (asserted
    by jaxpr inspection, the acceptance criterion);
  * the compiled-executable cache: a second call with the same shape bucket
    re-traces nothing.
"""
import numpy as np
import jax
import jax.numpy as jnp
import jax.random as jr
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel.compat import make_mesh, shard_map
from repro.sort import (
    ShardCtx, SortSpec, exec_cache, get_partitioner, sort, sort_batched)

# per-algorithm spec tweaks making every baseline exact on 8 host shards
# (mirrors tests/test_sort_api.py)
ALGO_SPECS = {
    "hss": dict(),
    "sample_random": dict(eps=0.1, out_slack=1.3),
    "sample_regular": dict(eps=0.2, out_slack=1.3),
    "ams": dict(eps=0.1, out_slack=1.3),
    "multistage": dict(),
}
B, N = 3, 8 * 128


def _check_matches_sequential(xs, spec):
    """sort_batched(xs) must match per-request sort() bit for bit."""
    out = sort_batched(jnp.asarray(xs), spec)
    for b in range(xs.shape[0]):
        seq = sort(jnp.asarray(xs[b]), spec)
        np.testing.assert_array_equal(out.gather(b), seq.gather())
        assert int(out.overflow[b]) == int(seq.overflow)
    return out


@pytest.mark.parametrize("algo", sorted(ALGO_SPECS))
def test_batched_matches_sequential_all_partitioners(rng, algo):
    xs = np.stack([rng.permutation(1 << 14)[:N].astype(np.int32)
                   for _ in range(B)])
    _check_matches_sequential(
        xs, SortSpec(algorithm=algo, exchange="allgather",
                     **ALGO_SPECS[algo]))


@pytest.mark.parametrize("dtype", ["int32", "uint32", "float32"])
def test_batched_matches_sequential_dtypes(rng, dtype):
    if dtype == "int32":
        xs = np.stack([rng.permutation(1 << 14)[:N] for _ in range(B)]
                      ).astype(np.int32)
    elif dtype == "uint32":
        xs = (rng.integers(0, 1 << 14, size=(B, N)).astype(np.uint32)
              + np.uint32(3_000_000_000))   # above the signed range
    else:
        xs = (rng.standard_normal((B, N)) * 1e3).astype(np.float32)
    out = _check_matches_sequential(xs, SortSpec(exchange="allgather"))
    for b in range(B):
        np.testing.assert_array_equal(out.gather(b), np.sort(xs[b]))
        assert out.gather(b).dtype == xs.dtype


def test_batched_dense_exchange(rng):
    xs = np.stack([rng.permutation(1 << 14)[:N].astype(np.int32)
                   for _ in range(B)])
    _check_matches_sequential(xs, SortSpec())


def test_batched_stable_indices(rng):
    xs = rng.integers(0, 50, size=(B, N)).astype(np.int32)  # heavy dups
    out = sort_batched(jnp.asarray(xs),
                       SortSpec(exchange="allgather", stable=True))
    for b in range(B):
        np.testing.assert_array_equal(out.gather(b), np.sort(xs[b]))
        np.testing.assert_array_equal(out.gather_indices(b),
                                      np.argsort(xs[b], kind="stable"))


def test_batched_ragged_bucket_tail(rng):
    # request length not divisible by the shard count: every row is
    # sentinel-padded by the driver and trimmed per request on decode
    n = 8 * 100 + 5
    xs = np.stack([rng.permutation(n).astype(np.int32) for _ in range(B)])
    out = sort_batched(jnp.asarray(xs), SortSpec(exchange="allgather"))
    for b in range(B):
        g = out.gather(b)
        assert g.size == n
        np.testing.assert_array_equal(g, np.sort(xs[b]))


def test_batched_b1_degenerate(rng):
    xs = rng.permutation(N).astype(np.int32)[None]
    out = sort_batched(jnp.asarray(xs), SortSpec(exchange="allgather"))
    assert out.batch == 1
    np.testing.assert_array_equal(out.gather(0), np.sort(xs[0]))


def test_batched_list_input_length_buckets(rng):
    # mixed lengths: grouped by exact length, one launch per bucket,
    # results in input order
    arrs = [rng.permutation(8 * 64 + (i % 3)).astype(np.int32)
            for i in range(5)]
    outs = sort_batched(arrs, SortSpec(exchange="allgather"))
    assert len(outs) == len(arrs)
    for a, o in zip(arrs, outs):
        np.testing.assert_array_equal(o.gather(), np.sort(a))


def test_spec_batch_routes_sort(rng):
    xs = np.stack([rng.permutation(N).astype(np.int32) for _ in range(B)])
    out = sort(jnp.asarray(xs), SortSpec(exchange="allgather", batch=True))
    np.testing.assert_array_equal(out.gather(1), np.sort(xs[1]))


def test_executable_cache_hit_no_retrace(rng):
    # a shape bucket no other test uses, so the first call is the miss
    n = 8 * 97
    spec = SortSpec(exchange="allgather")
    xs = np.stack([rng.permutation(n).astype(np.int32) for _ in range(B)])
    sort_batched(jnp.asarray(xs), spec)
    traces, hits, misses = exec_cache.traces, exec_cache.hits, exec_cache.misses
    xs2 = np.stack([rng.permutation(n).astype(np.int32) for _ in range(B)])
    sort_batched(jnp.asarray(xs2), spec)   # same shape bucket, new data
    assert exec_cache.traces == traces     # no retrace
    assert exec_cache.hits == hits + 1
    assert exec_cache.misses == misses
    # a different shape bucket is a fresh entry, not a stale-program reuse
    xs3 = np.stack([rng.permutation(n + 8).astype(np.int32)
                    for _ in range(B)])
    out3 = sort_batched(jnp.asarray(xs3), spec)
    assert exec_cache.misses == misses + 1
    np.testing.assert_array_equal(out3.gather(0), np.sort(xs3[0]))


def _collective_counts(batch, *, p=8, n_local=128):
    """Primitive counts of the batched HSS shard program: total, and within
    the splitter-round scan body (per-round costs). Traversal lives in
    repro.analysis.jaxpr_walk (shared with the contracts lint)."""
    from repro.analysis.jaxpr_walk import find_round_scan, primitive_counts

    mesh = make_mesh((p,), ("sort",))
    part = get_partitioner("hss")
    ctx = ShardCtx(spec=SortSpec(), axis_names=("sort",), sizes=(p,),
                   rng=None)

    def per_shard(block, key):
        rng = jr.fold_in(key, jax.lax.axis_index("sort"))
        local_sorted = jnp.sort(block.reshape(batch, n_local), axis=-1)
        return part.sharded_batched(local_sorted, rng, ctx)[0]

    f = shard_map(per_shard, mesh=mesh, in_specs=(P(None, "sort"), P()),
                  out_specs=P(None, "sort"))
    jaxpr = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((batch, p, n_local), jnp.int32), jr.key(0))

    total = primitive_counts(jaxpr.jaxpr, {})
    round_body = find_round_scan(jaxpr.jaxpr)
    assert round_body is not None, "splitter-round scan not found"
    per_round = primitive_counts(round_body, {})
    return total, per_round


def test_collective_count_independent_of_batch():
    """Acceptance: one all_gather + one psum per splitter round, and one
    payload all_to_all for the dense exchange, for B=1 and B=8 alike."""
    total1, round1 = _collective_counts(1)
    total8, round8 = _collective_counts(8)
    for name in ("all_gather", "psum", "all_to_all"):
        assert total1.get(name, 0) == total8.get(name, 0), name
    assert round1.get("all_gather") == 1
    assert round1.get("psum") == 1
    assert round8.get("all_gather") == 1
    assert round8.get("psum") == 1
