"""The unified repro.sort front-door: adapters, registry, argsort/sort_kv."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sort import SortSpec, argsort, available_algorithms, sort, sort_kv
from repro.parallel.compat import make_mesh

# per-algorithm spec tweaks that make every baseline exact on 8 host shards
ALGO_SPECS = {
    "hss": dict(),
    "sample_random": dict(eps=0.1, out_slack=1.3),
    "sample_regular": dict(eps=0.2, out_slack=1.3),
    "ams": dict(eps=0.1, out_slack=1.3),
    "multistage": dict(),
}


def test_registry_covers_all_algorithms():
    assert set(ALGO_SPECS) <= set(available_algorithms())


@pytest.mark.parametrize("algo", sorted(ALGO_SPECS))
def test_every_algorithm_sorts_identically(rng, algo):
    """Acceptance: each registry algorithm produces the same sorted output
    through the one sort() entry point."""
    n = 8 * 1024
    x = rng.permutation(n).astype(np.int32)
    out = sort(jnp.asarray(x), SortSpec(algorithm=algo, exchange="allgather",
                                        **ALGO_SPECS[algo]))
    assert int(out.overflow) == 0
    np.testing.assert_array_equal(out.gather(), np.sort(x))


def test_float32_bijection_roundtrip(rng):
    n = 8 * 1024
    x = (rng.standard_normal(n) * 1e4).astype(np.float32)
    out = sort(jnp.asarray(x), SortSpec(exchange="allgather"))
    g = out.gather()
    assert g.dtype == np.float32
    assert int(out.overflow) == 0
    np.testing.assert_array_equal(g, np.sort(x))


def test_float64_bijection_roundtrip(rng):
    with jax.enable_x64(True):
        n = 8 * 512
        x = rng.standard_normal(n) * 1e6   # float64
        out = sort(jnp.asarray(x), SortSpec(exchange="allgather"))
        g = out.gather()
        assert g.dtype == np.float64
        np.testing.assert_array_equal(g, np.sort(x))


def test_sort_duplicate_heavy_without_manual_tagging(rng):
    """Acceptance: duplicate-heavy input through plain sort(), no caller-side
    tagging — the adapter auto-detects and stays exact AND balanced."""
    n = 8 * 1024
    x = rng.integers(0, 8, size=n).astype(np.int32)   # 8 distinct values
    out = sort(jnp.asarray(x), SortSpec(exchange="allgather"))
    assert int(out.overflow) == 0
    np.testing.assert_array_equal(out.gather(), np.sort(x))
    assert np.all(np.asarray(out.counts) <= (1 + 0.05) * n / 8 + 1)


def test_argsort_matches_numpy_stable(rng):
    n = 8 * 512
    x = rng.integers(0, 64, size=n).astype(np.int32)
    order = argsort(jnp.asarray(x), SortSpec(exchange="allgather"))
    np.testing.assert_array_equal(order, np.argsort(x, kind="stable"))


def test_sort_kv_permutes_payloads_under_heavy_duplicates(rng):
    n = 8 * 512
    keys = rng.integers(0, 4, size=n).astype(np.int32)  # 4 distinct keys
    values = rng.standard_normal((n, 3)).astype(np.float32)
    k, v = sort_kv(jnp.asarray(keys), values, SortSpec(exchange="allgather"))
    ref = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(k, keys[ref])
    np.testing.assert_array_equal(v, values[ref])


def test_uint32_keys_above_signed_range(rng):
    # unsigned keys whose minimum exceeds INT32_MAX: the rebase must happen
    # in the unsigned domain before narrowing to the signed pack dtype
    n = 8 * 512
    x = (rng.integers(0, 50, size=n).astype(np.uint32)
         + np.uint32(3_000_000_000))
    out = sort(jnp.asarray(x), SortSpec(exchange="allgather"))
    g = out.gather()
    assert g.dtype == np.uint32
    np.testing.assert_array_equal(g, np.sort(x))


def test_non_divisible_input_is_padded_and_trimmed(rng):
    n = 8 * 512 + 5
    x = rng.permutation(n).astype(np.int32)
    out = sort(jnp.asarray(x), SortSpec(exchange="allgather"))
    assert int(np.asarray(out.counts).sum()) == n
    np.testing.assert_array_equal(out.gather(), np.sort(x))


def test_dtype_max_key_never_silently_dropped(rng):
    # INT32_MAX collides with the untagged pipeline's sentinel; the adapter
    # must force tagging — and when the packing budget doesn't fit (no x64)
    # it must fail loudly rather than drop the key
    n = 8 * 512
    x = rng.permutation(n).astype(np.int32)
    x[0] = np.iinfo(np.int32).max
    with pytest.raises(ValueError, match="x64|sentinel"):
        sort(jnp.asarray(x), SortSpec(exchange="allgather"))
    with jax.enable_x64(True):
        out = sort(jnp.asarray(x), SortSpec(exchange="allgather"))
        np.testing.assert_array_equal(out.gather(), np.sort(x))


def test_sentinel_image_nan_not_silently_dropped(rng):
    # the NaN payload whose bijection image is INT32_MAX would be filtered
    # as a sentinel on the untagged path; the adapter must force tagging
    # (and, when the packing budget doesn't fit, fail loudly)
    n = 8 * 512
    x = rng.standard_normal(n).astype(np.float32)
    x[0] = np.array([0x7FFFFFFF], np.int32).view(np.float32)[0]
    with pytest.raises(ValueError, match="x64|sentinel"):
        sort(jnp.asarray(x), SortSpec(exchange="allgather"))


def test_padded_input_with_overflow_serves_no_sentinels(rng):
    # non-divisible input AND a dense exchange that drops keys: the sort is
    # lossy (reported), but pad sentinels must never appear as data
    n = 8 * 1024 + 3
    x = np.arange(n, dtype=np.int32)[::-1].copy()   # mirror exchange pattern
    out = sort(jnp.asarray(x), SortSpec(pair_factor=1.0))
    assert int(out.overflow) > 0
    g = out.gather()
    assert g.size == int(np.asarray(out.counts).sum())
    assert np.all(g < np.iinfo(np.int32).max)


def test_indices_track_original_positions(rng):
    n = 8 * 256
    x = rng.integers(0, 1000, size=n).astype(np.int32)
    out = sort(jnp.asarray(x), SortSpec(exchange="allgather", stable=True))
    order = out.gather_indices()
    np.testing.assert_array_equal(x[order], out.gather())
    assert np.array_equal(np.sort(order), np.arange(n))


def test_spec_kwargs_shorthand(rng):
    x = rng.permutation(8 * 256).astype(np.int32)
    out = sort(jnp.asarray(x), algorithm="sample_regular", eps=0.2,
               exchange="allgather", out_slack=1.3)
    np.testing.assert_array_equal(out.gather(), np.sort(x))


def test_multistage_honors_explicit_mesh(rng):
    # (4, 2) differs from the auto factoring of 8 = (2, 4)
    mesh = make_mesh((4, 2), ("outer", "inner"))
    x = rng.permutation(8 * 512).astype(np.int32)
    out = sort(jnp.asarray(x), SortSpec(algorithm="multistage", mesh=mesh,
                                        exchange="allgather"))
    assert int(out.overflow) == 0
    np.testing.assert_array_equal(out.gather(), np.sort(x))


def test_argsort_raises_on_exchange_overflow(rng):
    # reversed input + pair_factor 1.0 dense exchange drops keys; a silent
    # truncated permutation would be wrong, so argsort must raise
    n = 8 * 1024
    x = np.arange(n, dtype=np.int32)[::-1].copy()
    with pytest.raises(RuntimeError, match="dropped"):
        argsort(jnp.asarray(x), SortSpec(pair_factor=1.0))


def test_unknown_algorithm_rejected():
    with pytest.raises(ValueError, match="unknown sort algorithm"):
        sort(jnp.arange(8), algorithm="quicksort")


def test_legacy_pad_keeps_sentinel_keys():
    # raw-core path, non-divisible input containing the sentinel value: the
    # driver counts sentinel-valued data keys device-side before padding and
    # restores them into the post-sort counts, so the key is served as data
    # while the pads are stripped — with no host round-trip (the old
    # implementation blocked on a device sync and raised here)
    from repro.core import gather_sorted, hss_sort
    x = np.array([np.iinfo(np.int32).max, 5, 1, 9, 3, 7, 2], np.int32)
    res = hss_sort(jnp.asarray(x))
    np.testing.assert_array_equal(gather_sorted(res), np.sort(x))


def test_legacy_pad_keeps_many_sentinel_keys():
    # sentinel keys spanning multiple tail shards restore in order
    from repro.core import gather_sorted, hss_sort
    rng = np.random.default_rng(3)
    x = rng.integers(0, 1000, size=8 * 64 + 3).astype(np.int32)
    x[:17] = np.iinfo(np.int32).max
    res = hss_sort(jnp.asarray(x))
    np.testing.assert_array_equal(gather_sorted(res), np.sort(x))


def test_backcompat_core_shims(rng):
    """Acceptance: `from repro.core import hss_sort` still works."""
    from repro.core import gather_sorted, hss_sort
    x = rng.permutation(8 * 256).astype(np.int32)
    res = hss_sort(jnp.asarray(x))
    np.testing.assert_array_equal(gather_sorted(res), np.sort(x))


def test_grouping_counting_dispatch(rng):
    from repro.sort.grouping import counting_dispatch
    ids = jnp.asarray(rng.integers(-1, 4, size=128).astype(np.int32))
    order, slot, keep = counting_dispatch(ids, 4, 16)
    ids_np = np.asarray(ids)
    # kept entries land in their own group's bin, stable within group
    kept = np.asarray(keep)
    slots = np.asarray(slot)
    for g in range(4):
        in_bin = (slots // 16 == g) & kept
        src = np.asarray(order)[in_bin]
        assert np.all(ids_np[src] == g)
        assert np.all(np.diff(src) > 0)   # stable: input order preserved


# -- dtype edge cases through the device-side audit (DESIGN.md Section 9) --

@pytest.mark.parametrize("algo", sorted(ALGO_SPECS))
def test_int_extremes_audited_end_to_end(algo):
    # INT32 min/max clusters: max collides with the untagged sentinel, so
    # the adapter forces tagging (int64 packing under x64) — and the full
    # audit must still pass on every partitioner
    from repro.data.distributions import make_adversarial
    n = 8 * 256
    x = make_adversarial("DTYPE_EXTREME", n, seed=2, dtype=np.int32)
    with jax.enable_x64(True):
        out = sort(jnp.asarray(x),
                   SortSpec(algorithm=algo, exchange="allgather",
                            verify="full", **ALGO_SPECS[algo]))
        assert out.audit is not None and out.audit.ok
        np.testing.assert_array_equal(out.gather(), np.sort(x))


@pytest.mark.parametrize("algo", sorted(ALGO_SPECS))
def test_signed_zero_total_order_audited(rng, algo):
    n = 8 * 256
    x = rng.standard_normal(n).astype(np.float32)
    x[:32] = -0.0
    x[32:64] = 0.0
    rng.shuffle(x)
    out = sort(jnp.asarray(x),
               SortSpec(algorithm=algo, exchange="allgather",
                        verify="full", **ALGO_SPECS[algo]))
    assert out.audit is not None and out.audit.ok
    g = out.gather()
    np.testing.assert_array_equal(g, np.sort(x))
    # the bijection's total order: every -0.0 sorts strictly before +0.0
    zeros = g[g == 0.0]
    assert zeros.size == 64
    assert np.all(np.diff(np.signbit(zeros).astype(np.int8)) <= 0)


@pytest.mark.parametrize("algo", sorted(ALGO_SPECS))
def test_nan_payload_sort_kv_audited(rng, algo):
    # NaN keys ride sort_kv with their payloads intact: the bijection
    # orders them after +inf (numpy's NaN-last), tagging keeps the
    # permutation stable, and the kv audit fingerprints key AND value
    n = 8 * 256
    keys = rng.standard_normal(n).astype(np.float32)
    keys[rng.permutation(n)[:48]] = np.float32(np.nan)
    values = np.arange(n, dtype=np.float32)
    with jax.enable_x64(True):   # negative floats span the int32 packing budget
        k, v = sort_kv(jnp.asarray(keys), values,
                       SortSpec(algorithm=algo, exchange="allgather",
                                verify="full", **ALGO_SPECS[algo]))
    ref = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(k, keys[ref])
    np.testing.assert_array_equal(v, values[ref])
