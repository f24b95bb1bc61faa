"""Sort Benchmark records through `repro.sort.sort_records`, against the
plain NumPy reference (`record_reference.py`), on simulated host devices:
every answer equal to the reference byte for byte, payloads included, and
HSS's eps balance kept where many records share a key."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import record_reference as ref
from repro.analysis.jaxpr_walk import walk_eqns
from repro.core.splitters import lex_searchsorted
from repro.kernels import dispatch
from repro.kernels.merge.ops import concat_runs
from repro.parallel.compat import make_mesh
from repro.runtime import spans
from repro.sort import (RecordSortOutput, SortSpec, driver, pack_records,
                        sort_records, unpack_records)

EPS = 0.05
EDGE = np.array([0x00, 0x7F, 0x80, 0xFF], np.uint8)


def _mesh(p):
    return make_mesh((p,), ("sort",), devices=jax.devices()[:p])


def _records(n, seed=0, keys=None):
    rng = np.random.default_rng(seed)
    recs = rng.integers(0, 256, (n, 100), dtype=np.uint8)
    if keys is not None:
        recs[:, :10] = keys
    return recs


def _on_mesh(recs, p):
    mesh = _mesh(p)
    words = jax.device_put(pack_records(recs),
                           NamedSharding(mesh, P(None, "sort")))
    return words, SortSpec(mesh=mesh, eps=EPS)


def _sorted(recs, p) -> RecordSortOutput:
    words, spec = _on_mesh(recs, p)
    return sort_records(words, spec)


def _all_equal(n, rng):
    return np.full((n, 10), 0x5A, np.uint8)


def _half_shared(n, rng):
    keys = rng.integers(0, 256, (n, 10), dtype=np.uint8)
    keys[rng.permutation(n)[:n // 2]] = 0x80
    return keys


def _differ_in_byte_9(n, rng):
    keys = np.full((n, 10), 0xC3, np.uint8)
    keys[:, 9] = rng.integers(0, 256, n, dtype=np.uint8)
    return keys


def _uniform(n, rng):
    return rng.integers(0, 256, (n, 10), dtype=np.uint8)


KEYS = {"uniform": _uniform, "all_equal": _all_equal,
        "half_shared": _half_shared, "differ_in_byte_9": _differ_in_byte_9}


@pytest.mark.parametrize("p", [4, 2])
@pytest.mark.parametrize("keys", sorted(KEYS))
def test_records_equal_the_reference(p, keys):
    n = 2048 * p
    recs = _records(n, seed=11, keys=KEYS[keys](n, np.random.default_rng(3)))
    out = _sorted(recs, p)
    assert int(out.overflow) == 0
    got = out.gather()
    assert got.shape == (n, 100) and got.dtype == np.uint8
    assert ref.mismatched_records(got, ref.reference_sort(recs)) == 0
    # every record once, all 100 bytes unaltered
    assert sorted(map(bytes, got)) == sorted(map(bytes, recs))


@pytest.mark.parametrize("keys", ["all_equal", "half_shared",
                                  "differ_in_byte_9"])
def test_equal_keys_keep_the_eps_bound(keys):
    p, n = 4, 4096 * 4
    recs = _records(n, seed=5, keys=KEYS[keys](n, np.random.default_rng(7)))
    out = _sorted(recs, p)
    counts = np.asarray(out.counts)
    assert counts.sum() == n
    assert counts.max() <= (1 + EPS) * n / p, counts


def test_packing_orders_words_as_memcmp_orders_bytes():
    """Big-endian words: unsigned word order is the bytes' memcmp order,
    on the bytes where a signed or little-endian packing would break it."""
    grid = np.array(np.meshgrid(*[EDGE] * 3, indexing="ij")).reshape(3, -1).T
    n = len(grid) * 4
    recs = _records(n, seed=2)
    for j, pos in enumerate([(0, 1, 2), (3, 4, 5), (7, 8, 9), (0, 8, 9)]):
        recs[j * len(grid):(j + 1) * len(grid), :10] = 0x7F
        recs[j * len(grid):(j + 1) * len(grid), list(pos)] = grid
    words = np.asarray(pack_records(recs))
    assert words.shape == (25, n) and words.dtype == np.uint32
    by_bytes = sorted(range(n), key=lambda i: bytes(recs[i, :10]))
    by_words = np.lexsort((words[2] >> 16, words[1], words[0]))
    key_of = [bytes(recs[i, :10]) for i in range(n)]
    assert [key_of[i] for i in by_words] == [key_of[i] for i in by_bytes]
    assert np.array_equal(unpack_records(words), recs)
    assert np.array_equal(ref.records_from_words(words), recs)
    assert np.array_equal(np.asarray(pack_records(jnp.asarray(recs))), words)
    out = _sorted(recs[:n - n % 4], 4)
    assert ref.mismatched_records(
        out.gather(), ref.reference_sort(recs[:n - n % 4])) == 0


def test_all_ones_keys_are_records_not_padding():
    """A key of ten 0xFF bytes equals the exchange's filler words; the
    merge must still keep every such record."""
    n, p = 4096, 4
    keys = np.full((n, 10), 0xFF, np.uint8)
    keys[::3] = np.random.default_rng(1).integers(0, 256, (len(keys[::3]), 10))
    recs = _records(n, seed=9, keys=keys)
    recs[:, 10:12] = 0xFF            # word 2 all ones where the key is
    out = _sorted(recs, p)
    assert ref.mismatched_records(out.gather(),
                                  ref.reference_sort(recs)) == 0


@pytest.mark.parametrize("verify", ["cheap", "full"])
def test_verify_other_than_off_raises(verify):
    words, spec = _on_mesh(_records(64), 4)
    with pytest.raises(NotImplementedError, match="verify"):
        sort_records(words, spec, verify=verify)


@pytest.mark.parametrize("override", [{"algorithm": "ams"},
                                      {"exchange": "allgather"},
                                      {"on_overflow": "retry"}])
def test_unsupported_specs_raise(override):
    words, spec = _on_mesh(_records(64), 4)
    with pytest.raises(NotImplementedError):
        sort_records(words, spec, **override)


def test_device_bytes_and_one_chip():
    """(n, 100) uint8 on the device is packed there; one chip sorts
    locally and sends nothing."""
    recs = _records(1000, seed=4)
    out = sort_records(jnp.asarray(recs), SortSpec(mesh=_mesh(1)))
    assert ref.mismatched_records(out.gather(),
                                  ref.reference_sort(recs)) == 0
    c = out.counters()
    assert c[spans.ROUNDS_USED] == 0 and c[spans.WIRE_BYTES] == [0]


def test_counters_and_the_executable_cache():
    p, n = 4, 4096 * 4
    recs = _records(n, seed=8)
    words, spec = _on_mesh(recs, p)
    out = sort_records(words, spec)
    c = out.counters()
    assert set(c) == set(spans.COUNTERS)
    assert 1 <= c[spans.ROUNDS_USED] <= spec.hss_config().resolved_rounds(p)
    cap = spec.exchange_config().pair_cap(n // p, p)
    assert c[spans.WIRE_BYTES] == [(p - 1) * cap * 100] * p
    sent = np.asarray(c[spans.SENT_BYTES])
    assert np.all(sent > 0) and np.all(sent <= c[spans.WIRE_BYTES][0])
    assert np.all(sent % 100 == 0)
    traces = driver.exec_cache.traces
    again = sort_records(words, spec)
    assert driver.exec_cache.traces == traces      # a cache hit
    assert np.array_equal(np.asarray(again.shards), np.asarray(out.shards))


def test_record_launch_moves_payload_without_a_gather(monkeypatch):
    """Every gather in the record launch reads at most a sample's or the
    splitters' worth of rows; none applies a permutation to n records.
    Each scope is on its ops, the probe search among them."""
    cache = driver.ExecutableCache()
    monkeypatch.setattr(driver, "exec_cache", cache)
    p, n_local = 4, 4096
    words, spec = _on_mesh(_records(p * n_local, seed=6), p)
    sort_records(words, spec)
    (fn,) = cache._fns.values()
    jx = jax.make_jaxpr(fn)(words, jax.random.key(0))
    gathers = [e for e in walk_eqns(jx) if e.primitive.name == "gather"]
    assert gathers
    for e in gathers:
        assert e.outvars[0].aval.size < n_local, e.outvars[0].aval.shape
    sorts = [e for e in walk_eqns(jx) if e.primitive.name == "sort"]
    assert all(len(e.invars) <= 2 and e.params["num_keys"] == 1
               for e in sorts if e.invars[0].aval.size >= n_local)
    op_names = re.findall(r'op_name="([^"]*)"',
                          fn.lower(words, jax.random.key(0)).compile()
                          .as_text())
    for name in (spans.LOCAL_SORT, spans.SPLITTERS, spans.SPLITTER_ROUND,
                 spans.PROBE_SEARCH, spans.EXCHANGE, spans.MERGE):
        assert any(f"/{name}/" in o for o in op_names), name


def _lex_rows(a):
    return sorted(map(tuple, a.T.tolist()))


@pytest.mark.parametrize("num_keys", [1, 3])
def test_sort_planes_is_a_stable_lexicographic_sort(num_keys):
    rng = np.random.default_rng(0)
    planes = rng.integers(0, 4, (6, 500)).astype(np.uint32)
    planes[:, ::7] = 0xFFFFFFFF
    out, order = dispatch.sort_planes(jnp.asarray(planes), num_keys)
    want = np.lexsort(planes[:num_keys][::-1])    # stable
    assert np.array_equal(np.asarray(order), want)
    assert np.array_equal(np.asarray(out), planes[:, want])


def test_lex_searchsorted_matches_a_scan():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 3, (3, 300)).astype(np.uint32)
    keys = keys[:, np.lexsort(keys[::-1])]
    queries = rng.integers(0, 4, (40, 3)).astype(np.uint32)
    got = np.asarray(lex_searchsorted(tuple(jnp.asarray(keys)),
                                      jnp.asarray(queries)))
    rows = [tuple(r) for r in keys.T.tolist()]
    want = [sum(r < tuple(q) for r in rows) for q in queries.tolist()]
    assert got.tolist() == want


def test_concat_runs_lays_runs_end_to_end():
    runs = np.full((2, 3, 5), 0xFFFFFFFF, np.uint32)
    counts = np.array([2, 0, 4])
    for i, c in enumerate(counts):
        runs[:, i, :c] = np.arange(c) + 10 * i + 1
    got = np.asarray(concat_runs(jnp.asarray(runs), counts, 8))
    want = np.array([1, 2, 21, 22, 23, 24] + [0xFFFFFFFF] * 2, np.uint32)
    assert np.array_equal(got, np.stack([want, want]))
    assert np.array_equal(np.asarray(concat_runs(jnp.asarray(runs), counts,
                                                  4))[0], want[:4])
