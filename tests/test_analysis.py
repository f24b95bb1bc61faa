"""The static-analysis subsystem (DESIGN.md Section 11).

Every contract class must *fire*: each test builds a deliberately
violating toy program (an extra all_to_all, a collective in both branches
of a round-scan cond, a B-dependent psum count, a wrong gather width, an
oversized VMEM block, a host sync, an unkeyed retrace) and asserts the
corresponding checker reports exactly that violation — plus the matching
compliant twin, proving the checkers don't cry wolf. The purity tests
also pin the lazy heavy-stats materialization of semisort outputs.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import comms, contracts, jaxpr_walk, purity, vmem
from repro.analysis.contracts import CommsContract
from repro.parallel.compat import make_mesh, shard_map
from repro.sort import SortSpec, sort
from repro.sort.semisort import semisort

pytestmark = pytest.mark.analysis

AXIS, P_SHARDS, N_LOCAL = "sort", 8, 128


def _trace(body, *, batch=None):
    """Trace a toy per-shard body under shard_map, driver-style."""
    mesh = make_mesh((P_SHARDS,), (AXIS,))
    f = shard_map(body, mesh=mesh, in_specs=(P(AXIS),), out_specs=P(AXIS))
    shape = ((P_SHARDS, N_LOCAL) if batch is None
             else (batch, P_SHARDS, N_LOCAL))
    spec = P(AXIS) if batch is None else P(None, AXIS)
    if batch is not None:
        f = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec)
    return jax.make_jaxpr(f)(jax.ShapeDtypeStruct(shape, jnp.int32))


def _rules(report):
    return sorted({v.rule for v in report.violations})


# -------------------------------------------------------------- jaxpr_walk --

def test_walk_descends_into_cond_branches():
    def f(x):
        return jax.lax.cond(x[0] > 0, jnp.sin, jnp.cos, x)

    counts = jaxpr_walk.primitive_counts(jax.make_jaxpr(f)(jnp.ones(4)))
    assert counts.get("sin", 0) == 1
    assert counts.get("cos", 0) == 1
    assert counts.get("cond", 0) == 1


def test_find_round_scan_skips_gatherless_scans():
    def body(x):
        # a plain scan first — must NOT be picked as the round scan
        y, _ = jax.lax.scan(lambda c, _: (c + 1, ()), x, None, length=2)

        def round_fn(c, _):
            return c + jnp.sum(jax.lax.all_gather(c, AXIS)), ()
        out, _ = jax.lax.scan(round_fn, y, None, length=3)
        return out

    jx = _trace(body)
    round_body = jaxpr_walk.find_round_scan(jx)
    assert round_body is not None
    assert jaxpr_walk.primitive_counts(round_body).get("all_gather") == 1


# --------------------------------------------------------------- contracts --

def test_total_counts_fires_on_extra_all_to_all():
    def chatty(x):
        g = jnp.sum(jax.lax.all_gather(x, AXIS))
        y = jax.lax.all_to_all(                        # the contraband
            x.reshape(P_SHARDS, -1), AXIS, 0, 0)
        return x + g + jnp.sum(y)

    contract = CommsContract(name="toy", total_counts={
        "all_gather": 1, "all_to_all": 0})
    report = contracts.check_jaxpr(_trace(chatty), contract)
    assert not report.ok
    assert _rules(report) == ["total_counts"]
    assert any("all_to_all" in v.message for v in report.violations)


def test_total_counts_passes_compliant_twin():
    def quiet(x):
        return x + jnp.sum(jax.lax.all_gather(x, AXIS))

    contract = CommsContract(name="toy", total_counts={
        "all_gather": 1, "all_to_all": 0})
    contracts.check_jaxpr(_trace(quiet), contract).raise_if_failed()


def test_forbid_and_max_total_fire():
    def hop(x):
        y = jax.lax.ppermute(x, AXIS,
                             [(i, (i + 1) % P_SHARDS)
                              for i in range(P_SHARDS)])
        z = jax.lax.psum(x, AXIS) + jax.lax.psum(y, AXIS)
        return x + z

    contract = CommsContract(name="toy", forbid=("ppermute",),
                             max_total={"psum": 1})
    report = contracts.check_jaxpr(_trace(hop), contract)
    assert _rules(report) == ["forbid", "max_total"]


def _round_scan_body(converged_pure):
    """A 3-round splitter-style scan whose cond either keeps one branch
    collective-free (compliant) or psums in both branches (violating)."""
    def body(x):
        def round_fn(carry, _):
            probe = jnp.sum(jax.lax.all_gather(carry, AXIS))
            work = lambda c: c + jax.lax.psum(c, AXIS)
            done = (lambda c: c) if converged_pure else \
                   (lambda c: c - jax.lax.psum(c, AXIS))
            return jax.lax.cond(probe > 0, work, done, carry), ()
        out, _ = jax.lax.scan(round_fn, x, None, length=3)
        return out
    return body


def test_converged_branch_pure_fires_when_both_branches_communicate():
    contract = CommsContract(name="toy", converged_branch_pure=True,
                             round_collectives={"all_gather": 1})
    bad = contracts.check_jaxpr(_trace(_round_scan_body(False)), contract)
    assert _rules(bad) == ["converged_branch_pure"]
    good = contracts.check_jaxpr(_trace(_round_scan_body(True)), contract)
    good.raise_if_failed()


def test_round_collectives_and_cap_fire():
    contract = CommsContract(name="toy",
                             round_collectives={"all_gather": 2},
                             max_round_collectives=1)
    report = contracts.check_jaxpr(_trace(_round_scan_body(True)), contract)
    # 1 gather (want 2) and gather+psum = 2 collectives (cap 1)
    assert _rules(report) == ["max_round_collectives", "round_collectives"]


def test_round_scan_required_but_missing_fires():
    report = contracts.check_jaxpr(
        _trace(lambda x: x + jax.lax.psum(x, AXIS)),
        CommsContract(name="toy", round_collectives={"all_gather": 1}))
    assert _rules(report) == ["round_scan"]


def test_gather_widths_fire_on_unpruned_operand():
    def unpruned(x):
        return x + jnp.sum(jax.lax.all_gather(x, AXIS))   # full n_local wide

    contract = CommsContract(name="toy", gather_widths=(16,))
    report = contracts.check_jaxpr(_trace(unpruned), contract)
    assert _rules(report) == ["gather_widths"]

    def pruned(x):
        return x + jnp.sum(jax.lax.all_gather(x[..., :16], AXIS))

    contracts.check_jaxpr(
        _trace(pruned), contract)  # widths [16] == (16,)
    contracts.check_jaxpr(_trace(pruned), contract).raise_if_failed()


def test_batch_invariance_fires_on_b_dependent_psum():
    def make_program(b, fused):
        def body(xs):
            if fused:
                return xs + jax.lax.psum(xs, AXIS)   # one batched psum
            out = xs
            for i in range(b):                       # one psum per request
                out = out.at[i].add(jax.lax.psum(xs[i], AXIS))
            return out
        mesh = make_mesh((P_SHARDS,), (AXIS,))
        f = shard_map(body, mesh=mesh, in_specs=(P(None, AXIS),),
                      out_specs=P(None, AXIS))
        return f, (jax.ShapeDtypeStruct((b, P_SHARDS, N_LOCAL), jnp.int32),)

    contract = CommsContract(name="toy", batch_invariant=("psum",))
    bad = contracts.check_batch_invariance(
        lambda b: make_program(b, fused=False), contract, batches=(1, 8))
    assert _rules(bad) == ["batch_invariant"]
    assert "B=8" in bad.violations[0].message
    contracts.check_batch_invariance(
        lambda b: make_program(b, fused=True), contract,
        batches=(1, 8)).raise_if_failed()


def test_registry_rejects_conflicting_contract():
    shipped = contracts.get_contract("splitters:hss")
    assert shipped.total_counts == {"all_gather": 1, "psum": 1,
                                    "all_to_all": 0}
    with pytest.raises(ValueError, match="conflicting contract"):
        contracts.register_contract(
            "splitters:hss", CommsContract(name="splitters:hss"))
    # re-registering the identical contract is idempotent
    contracts.register_contract("splitters:hss", shipped)


# ------------------------------------------------------------------- comms --

def test_cost_model_multiplies_scan_trips():
    report = comms.analyze_jaxpr(_trace(_round_scan_body(True)), label="toy")
    gathers = [c for c in report.collectives if c.primitive == "all_gather"]
    assert len(gathers) == 1
    assert gathers[0].trips == 3                      # scan length
    assert gathers[0].axes == (AXIS,)
    assert "scan" in gathers[0].path
    assert gathers[0].total_bytes == 3 * gathers[0].operand_bytes
    assert report.counts()["all_gather"] == 1
    assert report.in_round_scan()
    assert "toy" in report.render()


def test_cost_model_unbounded_inside_while():
    def body(x):
        def cond_fn(c):
            return jnp.sum(c) > 0

        def body_fn(c):
            return c - jnp.abs(jax.lax.psum(c, AXIS))
        return jax.lax.while_loop(cond_fn, body_fn, x)

    report = comms.analyze_jaxpr(_trace(body), label="toy")
    (psum,) = [c for c in report.collectives if c.primitive == "psum"]
    assert psum.trips is None                          # data-dependent
    assert report.total_rounds() is None
    assert report.total_bytes() is None


# -------------------------------------------------------------------- vmem --

def test_vmem_budget_fires_on_oversized_block():
    with pytest.raises(vmem.VmemBudgetError) as e:
        vmem.block_sort_footprint(1 << 22, itemsize=4).check("tpu")
    # the failure message shows the arithmetic and the budget
    assert "10*4194304*4 + 2*4194304*4" in str(e.value)
    assert str(vmem.vmem_budget_bytes("tpu")) in str(e.value)


def test_vmem_budget_fires_on_oversized_histogram_tile():
    with pytest.raises(vmem.VmemBudgetError):
        vmem.histogram_footprint(tile=1 << 16, m=4096).check("tpu")


def test_shipped_kernel_configs_fit_the_budget():
    checked = vmem.check_kernel_budgets(platform="tpu", p=256,
                                        itemsizes=(4, 8))
    assert len(checked) == 8
    budget = vmem.vmem_budget_bytes("tpu")
    assert all(fp.vmem_bytes <= budget for fp in checked)
    families = {fp.family for fp in checked}
    assert families == {"bitonic_sort", "merge", "histogram"}


# ------------------------------------------------------------------ purity --

def test_sync_free_trace_fires_on_concretization():
    sds = jax.ShapeDtypeStruct((16,), jnp.int32)
    with pytest.raises(purity.HostSyncViolation, match="concretizes"):
        purity.assert_sync_free_trace(lambda x: x + int(jnp.sum(x)), sds)
    with pytest.raises(purity.HostSyncViolation, match="concretizes"):
        purity.assert_sync_free_trace(lambda x: x + np.asarray(x), sds)
    out = purity.assert_sync_free_trace(lambda x: jnp.sum(x), sds)
    assert out.shape == ()


def test_no_host_sync_guard_fires_on_materialization():
    # the runtime transfer guard only observes real device->host copies;
    # on host-resident (cpu) buffers it is structurally inert
    if not purity.transfer_guard_effective():
        pytest.skip("transfer guard is a no-op on the cpu backend")
    x = jnp.arange(16)
    jax.block_until_ready(x)
    with pytest.raises(purity.HostSyncViolation):
        purity.assert_no_host_sync(lambda: np.asarray(x))
    out = purity.assert_no_host_sync(
        lambda: jax.block_until_ready(jnp.sum(x)))
    assert int(out) == 120


def test_audit_retrace_flags_cache_bypass():
    f = jax.jit(lambda x: x + 1)   # never touches the executable cache
    with pytest.raises(purity.RetraceViolation, match="bypasses the cache"):
        purity.audit_retrace(lambda: f(jnp.arange(8)))


def test_audit_retrace_flags_unkeyed_caller(rng):
    # an "unkeyed" caller: every call lands in a fresh shape bucket, so the
    # warm repeat re-traces instead of hitting the cache
    sizes = iter([8 * 141, 8 * 142, 8 * 143])
    spec = SortSpec(exchange="allgather", tag=False)

    def call():
        n = next(sizes)
        return sort(jnp.asarray(rng.permutation(n).astype(np.int32)), spec)

    with pytest.raises(purity.RetraceViolation, match="re-traced"):
        purity.audit_retrace(call)


def test_audit_retrace_passes_warm_front_door(rng):
    n = 8 * 139
    spec = SortSpec(exchange="allgather", tag=False)

    def call():
        return sort(jnp.asarray(rng.permutation(n).astype(np.int32)), spec)

    out = purity.audit_retrace(call)
    np.testing.assert_array_equal(np.sort(out.gather()), out.gather())


# --------------------------------------------- semisort lazy heavy stats --

def test_semisort_heavy_stats_materialize_lazily(rng):
    """Regression pin for the eager-sync fix: semisort() returns without
    materializing heavy stats; the decode runs on first property access
    and the values match a host-side recount exactly."""
    x = rng.integers(0, 50, size=8 * 137).astype(np.int32)
    out = semisort(jnp.asarray(x))
    assert out._decode is not None          # nothing materialized yet
    keys, counts_ = out.heavy_keys, out.heavy_counts
    assert out._decode is None              # one-shot materialization
    assert keys is out.heavy_keys           # idempotent: same arrays back
    assert counts_ is out.heavy_counts
    for k, c in zip(np.asarray(keys), np.asarray(counts_)):
        assert c == np.sum(x == k), (k, c)
