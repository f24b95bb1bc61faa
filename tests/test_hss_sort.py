"""End-to-end distributed HSS sort correctness on host devices."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (ExchangeConfig, HSSConfig, gather_sorted, hss_sort)
from repro.parallel.compat import make_mesh


def check_sorted(x, res, eps, exact=True):
    x = np.asarray(x)
    g = gather_sorted(res)
    p = res.shards.shape[0]
    if exact:
        assert int(res.overflow) == 0
        assert g.size == x.size
        np.testing.assert_array_equal(np.sort(g), np.sort(x))
    assert np.all(np.diff(g.astype(np.float64)) >= 0)
    cap = (1 + eps) * x.size / p
    assert np.all(np.asarray(res.counts) <= cap + 1)


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("eps", [0.02, 0.1])
def test_hss_sort_uniform(rng, dtype, eps):
    n = 8 * 2048
    if dtype == np.int32:
        x = rng.permutation(n).astype(dtype)
    else:
        x = rng.permutation(n).astype(dtype) / n
    res = hss_sort(jnp.asarray(x), hss_cfg=HSSConfig(eps=eps))
    check_sorted(x, res, eps)


def test_hss_sort_presorted(rng):
    # Pre-sorted globally balanced input: splitter intervals collapse fast and
    # the exchange moves (almost) nothing off-diagonal.
    n = 8 * 2048
    x = np.arange(n, dtype=np.int32)
    res = hss_sort(jnp.asarray(x), hss_cfg=HSSConfig(eps=0.05),
                   ex_cfg=ExchangeConfig(pair_factor=8.0))
    check_sorted(x, res, 0.05)


def test_hss_sort_reverse_and_skew(rng):
    n = 8 * 2048
    rev = np.arange(n, dtype=np.int32)[::-1].copy()
    # reversed input: every shard's keys go to the mirror shard; per-pair
    # counts hit n_local for one destination — needs pair_factor p or the
    # allgather strategy. Use allgather (the robust fallback).
    res = hss_sort(jnp.asarray(rev), hss_cfg=HSSConfig(eps=0.05),
                   ex_cfg=ExchangeConfig(strategy="allgather"))
    check_sorted(rev, res, 0.05)


def test_hss_adversarial_distribution(rng):
    # half the mass in a tiny range (paper's SKEW1), distinct keys
    n = 8 * 2048
    a = rng.permutation(n // 2).astype(np.int64)
    b = rng.permutation(np.arange(n // 2)) * 10_000 + 2_000_000
    x = np.concatenate([a, b]).astype(np.int32)
    rng.shuffle(x)
    res = hss_sort(jnp.asarray(x), hss_cfg=HSSConfig(eps=0.05),
                   ex_cfg=ExchangeConfig(pair_factor=6.0))
    check_sorted(x, res, 0.05)


def test_hss_allgather_matches_dense(rng):
    n = 8 * 1024
    x = rng.permutation(n).astype(np.int32)
    r1 = hss_sort(jnp.asarray(x), seed=3)
    r2 = hss_sort(jnp.asarray(x), seed=3,
                  ex_cfg=ExchangeConfig(strategy="allgather"))
    np.testing.assert_array_equal(gather_sorted(r1), gather_sorted(r2))


def test_hss_warm_start_reduces_rounds(rng):
    """The ChaNGa trick: previous splitters as initial probes (paper 7.3)."""
    n = 8 * 4096
    x = rng.permutation(n).astype(np.int32)
    res = hss_sort(jnp.asarray(x), hss_cfg=HSSConfig(eps=0.05), seed=0)
    cold_rounds = int(res.stats.rounds_used)
    # drift the data slightly and re-sort warm-started from old splitters
    x2 = x + rng.integers(-3, 4, size=n).astype(np.int32)
    x2 = np.asarray(jnp.asarray(x2))
    probes = jnp.sort(res.splitter_keys)
    res2 = hss_sort(jnp.asarray(x2), hss_cfg=HSSConfig(eps=0.05), seed=1,
                    initial_probes=probes)
    warm_rounds = int(res2.stats.rounds_used)
    g = gather_sorted(res2)
    assert np.all(np.diff(g.astype(np.int64)) >= 0)
    assert warm_rounds <= cold_rounds
    # warm start must already nearly satisfy everything in round 1
    assert int(res2.stats.gamma_size[0]) < n // 8


def test_hss_two_devices(rng):
    n = 2 * 512
    x = rng.permutation(n).astype(np.int32)
    mesh = make_mesh((2,), ("sort",), devices=jax.devices()[:2])
    res = hss_sort(jnp.asarray(x), mesh=mesh)
    check_sorted(x, res, 0.05)


def test_hss_single_device(rng):
    x = rng.permutation(256).astype(np.int32)
    mesh = make_mesh((1,), ("sort",), devices=jax.devices()[:1])
    res = hss_sort(jnp.asarray(x), mesh=mesh)
    np.testing.assert_array_equal(np.asarray(res.shards[0]), np.sort(x))


def test_overflow_reported_when_capacity_too_small(rng):
    n = 8 * 2048
    x = np.arange(n, dtype=np.int32)[::-1].copy()  # mirror exchange pattern
    res = hss_sort(jnp.asarray(x), hss_cfg=HSSConfig(eps=0.05),
                   ex_cfg=ExchangeConfig(pair_factor=1.0))
    assert int(res.overflow) > 0  # detected, not silent


@pytest.mark.parametrize("n_valid", [None, 700])
def test_ragged_row_aligned_chunks(rng, n_valid):
    """The ragged exchange's staging: each destination slice starts on a
    row of RAGGED_ROW keys and nothing else but sentinels is sent."""
    from repro.core.exchange import (RAGGED_ROW, destination_slices,
                                     row_aligned_chunks)
    n, p = 1000, 4
    local = np.sort(rng.integers(0, 10**6, n).astype(np.int32))
    splitters = np.sort(rng.choice(local[:600], p - 1, replace=False))
    starts, counts = destination_slices(jnp.asarray(local),
                                        jnp.asarray(splitters), n_valid)
    rows = -(-n // RAGGED_ROW) + p
    staged, row_starts, row_counts = row_aligned_chunks(
        jnp.asarray(local), starts, counts, rows)
    assert staged.shape == (rows, RAGGED_ROW)
    flat = np.asarray(staged).reshape(-1)
    starts, counts, row_starts, row_counts = map(
        np.asarray, (starts, counts, row_starts, row_counts))
    np.testing.assert_array_equal(row_counts, -(-counts // RAGGED_ROW))
    sent = np.zeros(flat.shape, bool)
    for d in range(p):
        lo = row_starts[d] * RAGGED_ROW
        assert row_starts[d] + row_counts[d] <= rows
        np.testing.assert_array_equal(
            flat[lo:lo + counts[d]], local[starts[d]:starts[d] + counts[d]])
        sent[lo:lo + counts[d]] = True
    assert np.all(flat[~sent] == np.iinfo(np.int32).max)
    assert sent.sum() == (n if n_valid is None else n_valid)


def _exchange_model(local, splitters, n_valid, cap, out_cap):
    """NumPy model of a capacity-padded exchange over p sorted shards: each
    source sends destination d the first min(count, cap) keys of its slice
    [searchsorted(splitters), n_valid); each destination keeps the first
    out_cap of what it received, sorted. Returns every destination's
    sentinel-padded output, its kept count, and the overflow all share."""
    p, n = local.shape
    recv = [[] for _ in range(p)]
    overflow = 0
    for s in range(p):
        b = np.minimum(np.searchsorted(local[s], splitters, side="left"),
                       n_valid)
        starts, ends = np.r_[0, b], np.r_[b, n_valid]
        for d in range(p):
            kept = min(ends[d] - starts[d], cap)
            overflow += ends[d] - starts[d] - kept
            recv[d].append(local[s, starts[d]:starts[d] + kept])
    out = np.full((p, out_cap), np.iinfo(local.dtype).max, local.dtype)
    counts = np.zeros(p, np.int32)
    for d in range(p):
        keys = np.sort(np.concatenate(recv[d]))[:out_cap]
        overflow += sum(map(len, recv[d])) - len(keys)
        out[d, :len(keys)] = keys
        counts[d] = len(keys)
    return out, counts, overflow


@pytest.mark.parametrize("strategy", ["dense", "dense_spill", "dense_batched"])
@pytest.mark.parametrize("split", ["balanced", "skewed", "n_valid"])
def test_dense_exchange_matches_send_side_model(rng, strategy, split):
    """The dense exchanges' send buffers hold each destination's slice,
    capped at pair_cap: what every destination receives, keeps and counts
    as overflow matches the NumPy model bit for bit. The skewed split puts
    one destination's count past pair_cap (dense drops and counts the
    excess, dense_spill carries it on its spill channel)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.exchange import exchange_dense, exchange_dense_spill
    from repro.parallel.compat import shard_map

    p, n, rows = 4, 256, 2 if strategy == "dense_batched" else 1
    n_valid = 200 if split == "n_valid" else n
    sent = np.iinfo(np.int32).max
    local = np.sort(rng.integers(0, 1000, (p, rows, n)), axis=-1)
    local = local.astype(np.int32)
    local[..., n_valid:] = sent
    splitters = np.array([900, 950, 975] if split == "skewed"
                         else [250, 500, 750], np.int32)
    splitters = np.stack([splitters + r for r in range(rows)])
    cfg = ExchangeConfig(strategy=strategy.replace("_batched", ""))
    cap = n if strategy == "dense_spill" else cfg.pair_cap(n, p)
    out_cap = cfg.out_cap(n, p, 0.05)
    fn = {"dense": exchange_dense, "dense_spill": exchange_dense_spill,
          "dense_batched": jax.vmap(functools.partial(
              exchange_dense, axis_name="sort", p=p, cfg=cfg, eps=0.05,
              n_valid=n_valid))}[strategy]

    def body(shard, spl):
        if strategy == "dense_batched":
            return tuple(v[None] for v in fn(shard[0], spl))
        return tuple(v[None] for v in fn(
            shard[0, 0], spl[0], axis_name="sort", p=p, cfg=cfg, eps=0.05,
            n_valid=n_valid))

    mesh = make_mesh((p,), ("sort",), devices=jax.devices()[:p])
    out, counts, overflow = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("sort"), P()),
        out_specs=(P("sort"),) * 3))(jnp.asarray(local),
                                     jnp.asarray(splitters))
    out, counts, overflow = (np.asarray(out), np.asarray(counts),
                             np.asarray(overflow))
    if strategy != "dense_batched":
        out, counts, overflow = out[:, None], counts[:, None], overflow[:, None]
    for r in range(rows):
        want_out, want_counts, want_ovf = _exchange_model(
            local[:, r], splitters[r], n_valid, cap, out_cap)
        np.testing.assert_array_equal(out[:, r], want_out)
        np.testing.assert_array_equal(counts[:, r], want_counts)
        assert np.all(overflow[:, r] == want_ovf)
        if split == "skewed":   # destination 0's slice is past pair_cap
            first = [np.searchsorted(row, splitters[r, 0])
                     for row in local[:, r]]
            assert min(first) > cfg.pair_cap(n, p)
