"""Hypothesis property tests over the system's invariants.

Invariants checked on the *sharded* implementation:
  I1 output is a permutation of the input (no loss, no duplication)
  I2 output is globally sorted
  I3 every shard holds <= (1+eps) N/p keys (globally balanced splitting)
  I4 reported overflow == 0 implies exactness (the contract callers rely on)
  I5 splitter ranks are within the target tolerance (paper's T_i ranges)
and on the simulator:
  I6 interval-union size is exactly the size of the union (vs brute force)
"""
import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import ExchangeConfig, HSSConfig, gather_sorted, hss_sort
from repro.core.common import interval_union_size
from repro.parallel.compat import make_mesh


@st.composite
def key_arrays(draw):
    p = draw(st.sampled_from([2, 4, 8]))
    n_local = draw(st.sampled_from([64, 256, 1024]))
    seed = draw(st.integers(0, 2 ** 16))
    rng = np.random.default_rng(seed)
    kind = draw(st.sampled_from(["perm", "gauss", "clustered"]))
    n = p * n_local
    if kind == "perm":
        x = rng.permutation(n * 4)[:n].astype(np.int32)
    elif kind == "gauss":
        x = np.unique((rng.standard_normal(4 * n) * 1e6).astype(np.int32))
        rng.shuffle(x)
        x = x[:n]
        if x.size < n:
            x = np.concatenate([x, np.arange(n - x.size) + 2 ** 30]).astype(np.int32)
    else:
        base = rng.integers(0, 50, size=n).astype(np.int64) * 100000
        x = np.unique(base + np.arange(n))
        rng.shuffle(x)
        x = x[:n].astype(np.int32)
    return p, n_local, x


@given(key_arrays(), st.sampled_from([0.02, 0.1, 0.5]))
@settings(max_examples=15, deadline=None)
def test_sort_invariants(arr, eps):
    import jax
    p, n_local, x = arr
    mesh = make_mesh((p,), ("sort",), devices=jax.devices()[:p])
    res = hss_sort(jnp.asarray(x), mesh=mesh, hss_cfg=HSSConfig(eps=eps),
                   ex_cfg=ExchangeConfig(strategy="allgather"))
    g = gather_sorted(res)
    n = x.size
    assert int(res.overflow) == 0                      # I4
    np.testing.assert_array_equal(np.sort(g), np.sort(x))  # I1
    assert np.all(np.diff(g.astype(np.int64)) >= 0)    # I2
    if p > 1:
        assert np.all(np.asarray(res.counts) <= (1 + eps) * n / p + 1)  # I3
        tol = max(1, int(n * eps / (2 * p)))
        targets = np.arange(1, p) * n // p
        ranks = np.asarray(res.splitter_ranks, np.int64)
        assert np.all(np.abs(ranks - targets) <= tol)  # I5


@given(st.integers(0, 2 ** 16), st.integers(2, 64))
@settings(max_examples=50, deadline=None)
def test_interval_union_matches_bruteforce(seed, m):
    rng = np.random.default_rng(seed)
    n = 1000
    lo = np.sort(rng.integers(0, n, size=m))
    width = rng.integers(0, 60, size=m)
    hi = np.minimum(lo + width, n)
    hi = np.maximum.accumulate(hi)  # monotone as in the algorithm
    got = int(interval_union_size(lo.astype(np.int64), hi.astype(np.int64)))
    cover = np.zeros(n + 1, bool)
    for a, b in zip(lo, hi):
        cover[a:b] = True
    assert got == int(cover.sum())


# I7: the audit's multiset fingerprint (DESIGN.md Section 9) — lanes are
# equal iff the multisets are equal (equality direction exact; the
# inequality direction holds with prob ~1 - 2^-32L, so a drawn
# counterexample would be a genuine lane-collision bug, not flake)
@st.composite
def multiset_pairs(draw):
    rng = np.random.default_rng(draw(st.integers(0, 2 ** 16)))
    n = draw(st.sampled_from([3, 64, 511]))
    x = rng.integers(-2 ** 31, 2 ** 31, size=n).astype(np.int32)
    same = draw(st.booleans())
    if same:
        y = rng.permutation(x)
    else:
        y = x.copy()
        y[int(draw(st.integers(0, n - 1)))] ^= np.int32(
            1 << draw(st.integers(0, 30)))
        rng.shuffle(y)
    return x, y, same


@given(multiset_pairs(), st.sampled_from([2, 4]))
@settings(max_examples=40, deadline=None)
def test_fingerprint_iff_multiset(pair, n_lanes):
    from repro.sort.verify import fingerprint_lanes
    x, y, same = pair
    fx = np.asarray(fingerprint_lanes(jnp.asarray(x), n_lanes))
    fy = np.asarray(fingerprint_lanes(jnp.asarray(y), n_lanes))
    assert same == (np.array_equal(np.sort(x), np.sort(y)))
    assert np.array_equal(fx, fy) == same
