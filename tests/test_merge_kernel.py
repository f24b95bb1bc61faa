"""k-way merge kernel parity: Pallas (interpret=True) vs the jnp.sort oracle.

The merge kernels' contract is bit-identical equality with a full sort over
the same entries (sentinel padding included), across dtypes, degenerate run
shapes, and both the equal-capacity and ragged layouts — plus the dispatch
layer that selects between the kernels and the XLA primitives.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.common import hi_sentinel
from repro.kernels import dispatch
from repro.kernels.bitonic_sort import ops as bops
from repro.kernels.merge import kernel as mk
from repro.kernels.merge import ops as mops
from repro.kernels.merge import ref as mref

pytestmark = pytest.mark.kernels


def _keys(rng, n, dtype):
    if np.issubdtype(dtype, np.floating):
        return (rng.standard_normal(n) * 1e3).astype(dtype)
    info = np.iinfo(dtype)
    lo = 0 if info.min == 0 else -2 ** 28
    return rng.integers(lo, 2 ** 28, size=n).astype(dtype)


def _sorted_runs(rng, k, r, dtype):
    return np.sort(_keys(rng, k * r, dtype).reshape(k, r), axis=1)


# ------------------------------------------------------------ equal runs
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
@pytest.mark.parametrize("k,r", [(2, 64), (8, 128), (16, 32)])
def test_merge_sorted_runs_matches_oracle(rng, dtype, k, r):
    runs = _sorted_runs(rng, k, r, dtype)
    got = mops.merge_sorted_runs(jnp.asarray(runs), interpret=True)
    want = mref.merge_sorted_runs_ref(jnp.asarray(runs))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k", [1, 3, 5, 7, 11])
def test_merge_non_power_of_two_run_count(rng, k):
    runs = _sorted_runs(rng, k, 50, np.int32)   # r not a power of two either
    got = mops.merge_sorted_runs(jnp.asarray(runs), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.sort(runs.reshape(-1)))


@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
def test_merge_sentinel_padded_tails(rng, dtype):
    # ragged real lengths inside equal-capacity rows, sentinel-filled tails
    k, r = 6, 40
    sent = np.asarray(hi_sentinel(jnp.dtype(dtype)))
    runs = np.full((k, r), sent, dtype)
    lens = [0, 1, r, 17, 5, 39]     # includes empty and single-key runs
    for i, m in enumerate(lens):
        runs[i, :m] = np.sort(_keys(rng, m, dtype))
    got = mops.merge_sorted_runs(jnp.asarray(runs), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.sort(runs.reshape(-1)))


def test_merge_single_key_runs(rng):
    # r == 1 degenerates the merge tree into a plain sort of k keys
    runs = _keys(rng, 13, np.int32).reshape(13, 1)
    got = mops.merge_sorted_runs(jnp.asarray(runs), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.sort(runs.reshape(-1)))


def test_merge_flat_runs_matches_oracle(rng):
    run = 96
    x = np.sort(_keys(rng, 8 * run, np.float32).reshape(-1, run), axis=1)
    got = mops.merge_flat_runs(jnp.asarray(x.reshape(-1)), run=run,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.sort(x.reshape(-1)))


# ------------------------------------------------- HBM-resident merge pass
@pytest.mark.parametrize("vmem_block,cols", [(64, 32), (256, 64), (1024, 256)])
def test_merge_pass_hbm_matches_vmem_network(rng, vmem_block, cols):
    # same comparator network, chunked through HBM: bit-identical
    run = 512
    x = np.sort(_keys(rng, 8 * run, np.float32).reshape(-1, run),
                axis=1).reshape(-1)
    pairs = bops._flip_odd_runs(jnp.asarray(x), run)   # bitonic pairs
    got = mk.merge_level(pairs, run, vmem_block=vmem_block, row_len=2 * run,
                         cols=cols, interpret=True)
    want = np.sort(x.reshape(-1, 2 * run), axis=1).reshape(-1)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_merge_tree_above_vmem_ceiling(rng):
    # tiny forced VMEM ceiling: the merge tree finishes with strided HBM
    # passes instead of ever falling back to an XLA sort
    runs = _sorted_runs(rng, 16, 512, np.int32)
    got = mops.merge_sorted_runs(jnp.asarray(runs), vmem_block=128,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.sort(runs.reshape(-1)))


# ------------------------------------------------------------ ragged runs
def _ragged_buf(rng, cap, counts, dtype):
    sent = np.asarray(hi_sentinel(jnp.dtype(dtype)))
    buf = np.full(cap, sent, dtype)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    for s, c in zip(starts, counts):
        buf[s:s + c] = np.sort(_keys(rng, c, dtype))
    return buf, jnp.asarray(starts), jnp.asarray(np.asarray(counts, np.int32))


def _gather_runs_ref(buf, starts, counts, slot):
    """The element-gather formula gather_runs replaced."""
    pos = np.arange(slot)[None, :]
    idx = np.clip(np.asarray(starts)[:, None] + pos, 0, buf.shape[0] - 1)
    return np.where(pos < np.asarray(counts)[:, None], buf[idx],
                    np.asarray(hi_sentinel(jnp.dtype(buf.dtype))))


@pytest.mark.parametrize("dtype,n,starts,counts,slot,batched", [
    (np.int32, 100, [0, 30, 55, 90], [30, 25, 35, 10], 40, False),
    (np.uint32, 100, [0, 30, 55, 90], [30, 25, 35, 10], 40, False),
    (np.float32, 100, [0, 30, 55, 90], [30, 25, 35, 10], 40, False),
    (np.int32, 64, [0, 10, 10, 40], [10, 0, 30, 24], 32, False),   # empty
    (np.int32, 64, [0, 40, 64], [40, 24, 0], 32, False),   # start at the end
    (np.int32, 64, [0, 32], [32, 32], 32, False),   # window ends at the end
    (np.float32, 24, [0, 5, 20], [5, 15, 4], 40, False),   # slot > len(buf)
    (np.int32, 96, [[0, 12, 50, 96], [0, 40, 41, 96], [0, 0, 0, 48]],
     [[12, 38, 46, 0], [40, 1, 48, 0], [0, 0, 48, 48]], 48, True),   # vmap
], ids=["int32", "uint32", "float32", "count0", "start_at_len",
        "ends_at_len", "slot_past_len", "vmap"])
def test_gather_runs_matches_element_gather(rng, dtype, n, starts, counts,
                                            slot, batched):
    """Contiguous window copies return what the element gather returned,
    wherever 0 <= start and start + count <= len(buf)."""
    starts = np.asarray(starts, np.int32)
    counts = np.asarray(counts, np.int32)
    if not batched:
        buf = np.sort(_keys(rng, n, dtype))
        got = mops.gather_runs(jnp.asarray(buf), jnp.asarray(starts),
                               jnp.asarray(counts), slot)
        assert got.shape == (len(starts), slot) and got.dtype == buf.dtype
        np.testing.assert_array_equal(
            np.asarray(got), _gather_runs_ref(buf, starts, counts, slot))
        return
    bufs = np.sort(_keys(rng, len(starts) * n, dtype).reshape(-1, n), axis=1)
    got = jax.vmap(mops.gather_runs, in_axes=(0, 0, 0, None))(
        jnp.asarray(bufs), jnp.asarray(starts), jnp.asarray(counts), slot)
    for b in range(len(bufs)):
        np.testing.assert_array_equal(
            np.asarray(got[b]),
            _gather_runs_ref(bufs[b], starts[b], counts[b], slot))


@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
def test_merge_ragged_runs_matches_oracle(rng, dtype):
    counts = [37, 0, 1, 80, 0, 23]    # empty and single-key runs included
    buf, starts, cnts = _ragged_buf(rng, 256, counts, dtype)
    got = mops.merge_ragged_runs(jnp.asarray(buf), starts, cnts, slot=128,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.sort(buf))


def test_merge_ragged_spill_falls_back_exactly(rng):
    # a run longer than the static slot diverts to the in-kernel full sort
    counts = [100, 4, 60]
    buf, starts, cnts = _ragged_buf(rng, 192, counts, np.int32)
    got = mops.merge_ragged_runs(jnp.asarray(buf), starts, cnts, slot=32,
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.sort(buf))


# --------------------------------------------------------------- dispatch
def test_dispatch_auto_resolves_by_backend():
    want = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert dispatch.resolve_policy("auto") == want
    assert dispatch.resolve_policy("pallas") == "pallas"
    assert dispatch.resolve_policy("xla") == "xla"
    with pytest.raises(ValueError, match="kernel_policy"):
        dispatch.resolve_policy("cuda")


def test_dispatch_backends_bit_identical(rng):
    runs = _sorted_runs(rng, 8, 64, np.int32)
    a = dispatch.merge_runs(jnp.asarray(runs), policy="xla")
    b = dispatch.merge_runs(jnp.asarray(runs), policy="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    x = jnp.asarray(_keys(rng, 1000, np.float32))
    np.testing.assert_array_equal(
        np.asarray(dispatch.local_sort(x, policy="xla")),
        np.asarray(dispatch.local_sort(x, policy="pallas")))

    probes = jnp.sort(x[::37])
    xs = jnp.sort(x)
    np.testing.assert_array_equal(
        np.asarray(dispatch.probe_ranks(xs, probes, policy="xla",
                                        assume_sorted=True)),
        np.asarray(dispatch.probe_ranks(xs, probes, policy="pallas",
                                        assume_sorted=True)))
    # the kernel counts, it does not search: unsorted keys rank identically
    np.testing.assert_array_equal(
        np.asarray(dispatch.probe_ranks(x, probes, policy="pallas")),
        np.asarray(dispatch.probe_ranks(xs, probes, policy="xla",
                                        assume_sorted=True)))


def test_dispatch_merge_ragged_bit_identical(rng):
    buf, starts, cnts = _ragged_buf(rng, 128, [20, 0, 44, 7], np.int32)
    a = dispatch.merge_ragged(jnp.asarray(buf), starts, cnts, policy="xla")
    b = dispatch.merge_ragged(jnp.asarray(buf), starts, cnts, policy="pallas",
                              slot=64)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- pipeline integration
def test_front_door_sort_with_pallas_policy(rng):
    # the whole pipeline (local sort, sample sorts, probe ranking, post-
    # exchange merge) on the Pallas path, interpret mode, 8 shards
    from repro.sort import SortSpec, sort
    x = rng.permutation(8 * 64).astype(np.int32)
    out = sort(jnp.asarray(x), SortSpec(kernel_policy="pallas", tag=False))
    np.testing.assert_array_equal(out.gather(), np.sort(x))


def test_exchange_merge_policies_agree(rng):
    # dense exchange end-to-end: pallas merge == xla merge, bit for bit
    from repro.sort import SortSpec, sort
    x = rng.permutation(8 * 64).astype(np.int32)
    a = sort(jnp.asarray(x), SortSpec(kernel_policy="xla", tag=False))
    b = sort(jnp.asarray(x), SortSpec(kernel_policy="pallas", tag=False))
    np.testing.assert_array_equal(np.asarray(a.shards), np.asarray(b.shards))
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))


# ------------------------------------------------- batched kernels (Sec 6.2)
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
def test_batched_local_sort_matches_rows(rng, dtype):
    # batch grid dimension: B rows, one launch per pass, per-row parity
    xs = _keys(rng, 3 * 1000, dtype).reshape(3, 1000)
    got = bops.local_sort_batched(jnp.asarray(xs), block=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.sort(xs, axis=1))


def test_batched_probe_ranks_matches_unbatched(rng):
    from repro.kernels.histogram import ops as hops
    keys = _keys(rng, 3 * 777, np.int32).reshape(3, 777)
    probes = np.sort(_keys(rng, 3 * 33, np.int32).reshape(3, 33), axis=1)
    got = hops.probe_ranks_batched(jnp.asarray(keys), jnp.asarray(probes),
                                   interpret=True)
    for b in range(3):
        np.testing.assert_array_equal(
            np.asarray(got[b]),
            np.asarray(hops.probe_ranks(jnp.asarray(keys[b]),
                                        jnp.asarray(probes[b]),
                                        interpret=True)))


@pytest.mark.parametrize("k,r", [(1, 64), (5, 37), (16, 32)])
def test_batched_merge_runs_matches_oracle(rng, k, r):
    runs = np.stack([_sorted_runs(rng, k, r, np.int32) for _ in range(3)])
    got = mops.merge_sorted_runs_batched(jnp.asarray(runs), interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.sort(runs.reshape(3, -1), axis=1))


def test_batched_dispatch_policies_bit_identical(rng):
    xs = jnp.asarray(_keys(rng, 4 * 500, np.int32).reshape(4, 500))
    np.testing.assert_array_equal(
        np.asarray(dispatch.local_sort_batched(xs, policy="pallas")),
        np.asarray(dispatch.local_sort_batched(xs, policy="xla")))


def test_front_door_sort_batched_with_pallas_policy(rng):
    # the whole batched pipeline on the Pallas path, interpret mode
    from repro.sort import SortSpec, sort_batched
    xs = np.stack([rng.permutation(8 * 64).astype(np.int32)
                   for _ in range(2)])
    out = sort_batched(jnp.asarray(xs),
                       SortSpec(kernel_policy="pallas", tag=False))
    for b in range(2):
        np.testing.assert_array_equal(out.gather(b), np.sort(xs[b]))


@pytest.mark.parametrize("slot,spills", [(64, False), (16, True)])
def test_batched_merge_ragged_matches_oracle(rng, slot, spills):
    # per-row ragged runs at different traced offsets; the spill case takes
    # the batch-wide full-sort fallback
    per_row = [[20, 0, 44, 7], [3, 31, 1, 9], [40, 40, 8, 16]]
    bufs, starts, cnts = zip(*[_ragged_buf(rng, 128, c, np.int32)
                               for c in per_row])
    buf = np.stack(bufs)
    got = mops.merge_ragged_runs_batched(
        jnp.asarray(buf), jnp.stack(starts), jnp.stack(cnts), slot=slot,
        interpret=True)
    assert spills == any(max(c) > slot for c in per_row)
    np.testing.assert_array_equal(np.asarray(got), np.sort(buf, axis=1))
    # the dispatch wrapper under vmap, as a batched launch runs it:
    # parity against the XLA path
    merged = {policy: np.asarray(jax.vmap(functools.partial(
        dispatch.merge_ragged, policy=policy, slot=slot))(
            jnp.asarray(buf), jnp.stack(starts), jnp.stack(cnts)))
        for policy in ("pallas", "xla")}
    np.testing.assert_array_equal(merged["pallas"], merged["xla"])
