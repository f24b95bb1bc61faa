"""Device-side output auditing + imbalance SLO (DESIGN.md Section 9).

Three contracts:

  * zero false positives — the fused audit passes on every clean run
    across the paper + adversarial distribution families, all five
    partitioners, single and batched launches;
  * every injected bit-flip is caught — `chaos.FaultPlan(corrupt_at=...)`
    XORs one bit into one output key *after* the sort pipeline, and the
    audit must flag it (raise / retry / fallback per `on_verify_failure`)
    without ever poisoning the compiled-executable cache;
  * the partition-quality SLO recovers or raises — duplicate pileups the
    untagged splitters cannot cut auto-route through tagging, weak
    sampling through bonus refinement, and only then `ImbalanceError`.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.data.distributions import ADVERSARIAL, make_adversarial, \
    make_distribution
from repro.runtime import chaos
from repro.sort import (BatchVerificationError, ImbalanceError, SortSpec,
                        VerificationError, exec_cache, sort, sort_batched)
from repro.sort.verify import fingerprint_lanes

N = 8 * 64

# per-algorithm spec tweaks making every baseline exact on 8 host shards
ALGO_SPECS = {
    "hss": dict(),
    "sample_random": dict(eps=0.1, out_slack=1.3),
    "sample_regular": dict(eps=0.2, out_slack=1.3),
    "ams": dict(eps=0.1, out_slack=1.3),
    "multistage": dict(),
}

# paper distributions + the adversarial family (shifted to 9-bit keys so
# the auto-tagging budget — key_bits + tag_bits <= 30 — always fits and
# duplicate pileups route through tagging instead of truncating)
DISTS = ("UNIF", "SKEW2", "GAUSS")
ADV = ("ALL_EQUAL", "PRESORTED", "SAWTOOTH", "ZIPF_HH")


def _mk(name: str, n: int = N, seed: int = 5) -> np.ndarray:
    if name in ADVERSARIAL:
        return (make_adversarial(name, n, seed=seed) >> 21).astype(np.int32)
    return make_distribution(name, n, seed=seed)


def _spec(algo: str, **kw) -> SortSpec:
    return SortSpec(algorithm=algo, exchange="allgather", verify="cheap",
                    **{**ALGO_SPECS[algo], **kw})


# -- zero false positives ---------------------------------------------------

@pytest.mark.parametrize("algo", sorted(ALGO_SPECS))
def test_audit_zero_false_positives_single(algo):
    spec = _spec(algo)
    for name in DISTS + ADV:
        x = _mk(name)
        out = sort(jnp.asarray(x), spec)
        assert out.audit is not None and out.audit.ok, (algo, name)
        np.testing.assert_array_equal(out.gather(), np.sort(x))


@pytest.mark.parametrize("algo", sorted(ALGO_SPECS))
def test_audit_zero_false_positives_batched(algo):
    # one plan serves the whole batch, so its rows must share a tagging
    # budget: small-range duplicate-heavy rows (a wide-range row would
    # push the joint packing budget past int32 and force the batch
    # untagged, where a pileup row genuinely truncates)
    xs = np.stack([_mk(name) for name in ("SKEW2", "ALL_EQUAL", "PRESORTED",
                                          "ZIPF_HH")])
    out = sort_batched(jnp.asarray(xs), _spec(algo))
    assert out.audit is not None and out.audit.ok, algo
    for b in range(xs.shape[0]):
        view = out.request(b)
        assert view.audit is not None and view.audit.ok
        np.testing.assert_array_equal(view.gather(), np.sort(xs[b]))


def test_audit_full_tier_single_and_batched(rng):
    x = rng.permutation(4 * N)[:N].astype(np.int32)
    out = sort(jnp.asarray(x), SortSpec(exchange="allgather", verify="full"))
    assert out.audit.ok and out.audit.tier == "full"
    outs = sort_batched(jnp.asarray(np.stack([x, x[::-1].copy()])),
                        SortSpec(exchange="allgather", verify="full"))
    assert outs.audit.ok


# -- every bit-flip is caught ----------------------------------------------

@pytest.mark.parametrize("algo", sorted(ALGO_SPECS))
def test_bit_flip_detected_single(rng, algo):
    x = rng.permutation(4 * N)[:N].astype(np.int32)
    with chaos.activate(chaos.FaultPlan(corrupt_at=True)):
        with pytest.raises(VerificationError):
            sort(jnp.asarray(x), _spec(algo, tag=False))
        assert chaos.stats()["corrupt_launches"] >= 1


def test_bit_flip_detected_batched_isolates_marked_row(rng):
    xs = np.stack([rng.permutation(4 * N)[:N].astype(np.int32)
                   for _ in range(4)])
    xs[2, 0] = -7   # rows are otherwise non-negative: -7 marks the victim
    with chaos.activate(chaos.FaultPlan(corrupt_at=True, corrupt_key=-7)):
        with pytest.raises(BatchVerificationError) as ei:
            sort_batched(jnp.asarray(xs), SortSpec(exchange="allgather",
                                                   verify="cheap", tag=False))
    row_ok = np.asarray(ei.value.row_ok)
    np.testing.assert_array_equal(row_ok, [True, True, False, True])
    # the per-row report pinpoints the same verdicts
    assert not ei.value.report.row(2).ok
    assert ei.value.report.row(0).ok


def test_transient_corruption_recovered_by_retry(rng):
    x = rng.permutation(4 * N)[:N].astype(np.int32)
    with chaos.activate(chaos.FaultPlan(corrupt_at=(0,))):
        out = sort(jnp.asarray(x),
                   SortSpec(exchange="allgather", verify="cheap",
                            on_verify_failure="retry", tag=False))
    np.testing.assert_array_equal(out.gather(), np.sort(x))
    assert out.audit.ok
    assert out.recovery.verify_failures == 1
    assert out.recovery.verify_retries == 1
    assert not out.recovery.verify_fallback


def test_transient_corruption_recovered_by_fallback(rng):
    x = rng.permutation(4 * N)[:N].astype(np.int32)
    with chaos.activate(chaos.FaultPlan(corrupt_at=(0,))):
        out = sort(jnp.asarray(x),
                   SortSpec(exchange="allgather", verify="cheap",
                            on_verify_failure="fallback", tag=False))
    np.testing.assert_array_equal(out.gather(), np.sort(x))
    assert out.recovery.verify_fallback
    assert out.recovery.verify_failures == 1


def test_persistent_corruption_exhausts_the_policy(rng):
    x = rng.permutation(4 * N)[:N].astype(np.int32)
    with chaos.activate(chaos.FaultPlan(corrupt_at=True)):
        with pytest.raises(VerificationError):
            sort(jnp.asarray(x),
                 SortSpec(exchange="allgather", verify="cheap",
                          on_verify_failure="retry", tag=False))


def test_corrupt_launches_never_poison_the_exec_cache(rng):
    xs = np.stack([rng.permutation(4 * N)[:N].astype(np.int32)
                   for _ in range(2)])
    spec = SortSpec(exchange="allgather", verify="cheap", tag=False)
    out = sort_batched(jnp.asarray(xs), spec)     # warm the shape bucket
    assert out.audit.ok
    h0, m0 = exec_cache.hits, exec_cache.misses
    with chaos.activate(chaos.FaultPlan(corrupt_at=True)):
        with pytest.raises(BatchVerificationError):
            sort_batched(jnp.asarray(xs), spec)
    # the corrupted launch compiled outside the cache: no counter moved
    assert (exec_cache.hits, exec_cache.misses) == (h0, m0)
    out = sort_batched(jnp.asarray(xs), spec)     # clean again, from cache
    assert out.audit.ok and exec_cache.hits == h0 + 1
    for b in range(2):
        np.testing.assert_array_equal(out.request(b).gather(),
                                      np.sort(xs[b]))


# -- partition-quality SLO --------------------------------------------------

def test_imbalance_recorded_on_recovery_stats(rng):
    x = rng.permutation(4 * N)[:N].astype(np.int32)
    out = sort(jnp.asarray(x), SortSpec(exchange="allgather", verify="cheap"))
    imb = out.recovery.achieved_imbalance
    assert imb is not None and 1.0 <= imb <= 1.2
    assert out.audit.achieved_imbalance is not None


def test_imbalance_slo_raises_on_untagged_pileup():
    # all-equal, explicit tag=False, enough out_slack that nothing drops:
    # the whole input lands on one shard (imbalance ~ p) and neither rung
    # of the ladder can fix it (tagging is explicitly disabled)
    xe = np.full(N, 42, np.int32)
    base = dict(verify="cheap", tag=False, exchange="allgather",
                out_slack=8.0)
    out = sort(jnp.asarray(xe), SortSpec(**base))
    assert out.audit.ok                       # lossless, just imbalanced
    assert out.recovery.achieved_imbalance > 4.0
    with pytest.raises(ImbalanceError) as ei:
        sort(jnp.asarray(xe), SortSpec(imbalance_slo=1.5, **base))
    assert ei.value.achieved > ei.value.slo


def test_imbalance_slo_met_via_tagging():
    # same pileup with tag=None: duplicate tagging splits the class and
    # the SLO holds without raising
    xe = np.full(N, 42, np.int32)
    out = sort(jnp.asarray(xe),
               SortSpec(verify="cheap", exchange="allgather", out_slack=8.0,
                        imbalance_slo=1.5))
    assert out.recovery.achieved_imbalance <= 1.5
    np.testing.assert_array_equal(out.gather(), xe)


def test_imbalance_slo_refine_rung(rng):
    # distinct keys + a deliberately starved sampler: tagging cannot help,
    # bonus refinement (2x total_sample) must bring the partition under
    # the SLO and stamp the recovery rung. The sampler seed is pinned to a
    # draw whose 8-key sample misses the SLO: other draws meet it unaided.
    xd = rng.permutation((np.arange(N // 2) * 9973).astype(np.int32))
    out = sort(jnp.asarray(xd),
               SortSpec(algorithm="sample_random", total_sample=8,
                        tag=False, exchange="allgather", out_slack=8.0,
                        verify="cheap", imbalance_slo=2.1, seed=2))
    assert out.recovery.imbalance_recovery == "refine"
    assert out.recovery.achieved_imbalance <= 2.1
    np.testing.assert_array_equal(out.gather(), np.sort(xd))


@pytest.mark.parametrize("name", sorted(set(ADVERSARIAL) - {"DTYPE_EXTREME"}))
def test_adversarial_family_meets_slo(name):
    # acceptance: every adversarial input serves within the SLO (directly
    # or via the auto-recovery ladder), audited, with the exact output
    x = _mk(name, seed=11)
    out = sort(jnp.asarray(x),
               SortSpec(exchange="allgather", verify="cheap", out_slack=2.0,
                        imbalance_slo=1.2))
    assert out.audit.ok
    assert float(np.max(out.recovery.achieved_imbalance)) <= 1.2
    np.testing.assert_array_equal(out.gather(), np.sort(x))


# -- fingerprint properties (numpy-level; the hypothesis variant lives in
# test_property.py and deepens the same invariant when hypothesis exists) --

def _lanes(x, n_lanes=4):
    return np.asarray(fingerprint_lanes(jnp.asarray(x), n_lanes))


def test_fingerprint_is_order_independent(rng):
    x = rng.integers(-2 ** 31, 2 ** 31 - 1, size=997, dtype=np.int64)
    x = x.astype(np.int32)
    perm = rng.permutation(x)
    np.testing.assert_array_equal(_lanes(x), _lanes(perm))


def test_fingerprint_sums_commute_with_sharding(rng):
    # the psum reduction: lane sums over shards == lanes of the whole
    x = rng.integers(0, 1 << 20, size=512).astype(np.int32)
    whole = _lanes(x)
    parts = sum(_lanes(s).astype(np.uint64) for s in np.split(x, 8))
    np.testing.assert_array_equal(whole, (parts & 0xFFFFFFFF).astype(np.uint32))


def test_fingerprint_detects_any_single_mutation(rng):
    x = rng.integers(0, 1 << 20, size=512).astype(np.int32)
    base = _lanes(x)
    for bit in (0, 5, 12, 30):
        y = x.copy()
        y[int(rng.integers(0, x.size))] ^= np.int32(1 << bit)
        assert np.any(_lanes(y) != base), f"bit {bit} flip went unnoticed"
