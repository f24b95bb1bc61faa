"""Implicit tagging (paper Section 6.3) unit tests."""
import numpy as np
import jax.numpy as jnp

from repro.core.tagging import (
    float32_to_sortable_int32, pack_tagged, sortable_int32_to_float32,
    unpack_tagged)


def test_float_sortable_bijection(rng):
    x = np.concatenate([
        rng.standard_normal(4096).astype(np.float32) * 1e6,
        np.array([0.0, 1e-38, -1e-38, np.inf, -np.inf], np.float32)])
    s = np.asarray(float32_to_sortable_int32(jnp.asarray(x)))
    # order preserved
    order = np.argsort(x, kind="stable")
    assert np.all(np.diff(s[order]) >= 0)
    back = np.asarray(sortable_int32_to_float32(jnp.asarray(s)))
    np.testing.assert_array_equal(back, x)
    # -0.0 and +0.0 get distinct adjacent encodings (-0.0 just below +0.0)
    z = np.asarray(float32_to_sortable_int32(
        jnp.asarray(np.array([-0.0, 0.0], np.float32))))
    assert z[0] == z[1] - 1


def test_pack_unpack_roundtrip(rng):
    p, n_local = 8, 1024
    keys = rng.integers(0, 2 ** 16, size=n_local).astype(np.int32)
    t = pack_tagged(jnp.asarray(keys), 3, p=p, n_local=n_local, key_bits=16)
    assert t.dtype == jnp.int32
    back = np.asarray(unpack_tagged(t, p=p, n_local=n_local))
    np.testing.assert_array_equal(back, keys)


def test_tagging_makes_duplicates_distinct():
    p, n_local = 4, 256
    zeros = jnp.zeros((n_local,), jnp.int32)
    tags = [np.asarray(pack_tagged(zeros, i, p=p, n_local=n_local, key_bits=1))
            for i in range(p)]
    allt = np.concatenate(tags)
    assert np.unique(allt).size == p * n_local


def test_tagging_order_is_key_major(rng):
    p, n_local = 4, 512
    keys = rng.integers(0, 2 ** 10, size=n_local).astype(np.int32)
    t = np.asarray(pack_tagged(jnp.asarray(keys), 2, p=p, n_local=n_local,
                               key_bits=10))
    order = np.argsort(t)
    assert np.all(np.diff(keys[order]) >= 0)  # sorting tags sorts keys


def test_float_corner_encodings_totally_ordered():
    # the DTYPE_EXTREME corners (float min, -1, -0.0, +0.0, 1, max) get
    # strictly increasing sortable-int encodings — the total order the
    # verified-sort dtype tests rely on
    corners = np.array([np.finfo(np.float32).min, -1.0, -0.0, 0.0, 1.0,
                        np.finfo(np.float32).max], np.float32)
    s = np.asarray(float32_to_sortable_int32(jnp.asarray(corners)))
    assert np.all(np.diff(s.astype(np.int64)) > 0)


# -- MoE dispatch bit-identity (the semisort migration's regression pins) ----
#
# repro.sort.grouping.counting_dispatch replaced the stable-argsort dispatch
# in repro.models.moe. The contract: for MoE-shaped ids (the only invalid id
# is -1) the counting path is BIT-identical — same permutation, same slots,
# same keeps, hence bit-identical expert outputs.

from repro.sort import grouping
from repro.sort.grouping import counting_dispatch, grouping_permutation
from repro.parallel.compat import make_mesh


def _dispatch_np(ids, n_groups, capacity, method):
    order, slot, keep = counting_dispatch(
        jnp.asarray(ids), n_groups, capacity, method=method)
    return np.asarray(order), np.asarray(slot), np.asarray(keep)


def test_grouping_permutation_matches_stable_argsort(rng):
    for _ in range(10):
        ids = rng.choice(np.arange(-1, 8), size=192).astype(np.int32)
        perm = np.asarray(grouping_permutation(jnp.asarray(ids), 8))
        np.testing.assert_array_equal(perm, np.argsort(ids, kind="stable"))


def test_counting_dispatch_bit_identical_moe_shapes(rng):
    """20 random MoE-shaped trials ({-1} u [0, E) ids): (order, slot, keep)
    agree bit-for-bit between the counting and legacy argsort methods."""
    E, cap = 8, 32
    for trial in range(20):
        ids = rng.choice(np.arange(-1, E),
                         size=256, p=[0.2] + [0.1] * E).astype(np.int32)
        a = _dispatch_np(ids, E, cap, "argsort")
        c = _dispatch_np(ids, E, cap, "counting")
        for x, y in zip(a, c):
            np.testing.assert_array_equal(x, y)


def test_counting_dispatch_bit_identical_under_capacity_overflow(rng):
    """Overflowing a group's capacity drops the SAME items (stable rank
    order) on both methods — the keep mask and overflow-row slots match."""
    E, cap = 4, 4          # 256 items into 4*4 slots: heavy overflow
    ids = rng.integers(-1, E, size=256).astype(np.int32)
    a = _dispatch_np(ids, E, cap, "argsort")
    c = _dispatch_np(ids, E, cap, "counting")
    for x, y in zip(a, c):
        np.testing.assert_array_equal(x, y)
    order, slot, keep = c
    assert np.sum(keep) == sum(min(cap, np.sum(ids == e)) for e in range(E))
    assert np.all(slot[~keep] == E * cap)     # overflow row


def test_counting_dispatch_rejects_unknown_method():
    import pytest
    with pytest.raises(ValueError, match="unknown dispatch method"):
        counting_dispatch(jnp.zeros((8,), jnp.int32), 2, 4, method="radix")


def _moe_smoke(rng, capacity_factor):
    import dataclasses as dc

    import jax
    from repro.configs import smoke_config
    from repro.models.moe import moe_ffn
    from repro.parallel.ctx import ParallelCtx

    cfg = dc.replace(smoke_config("phi3.5-moe-42b-a6.6b"),
                     n_experts=8, d_model=64, d_ff_expert=96,
                     moe_capacity_factor=capacity_factor)
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    params = {
        "router": jnp.asarray(rng.standard_normal((d, E)), jnp.float32) * 0.1,
        "w1": jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32) * 0.05,
        "w3": jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32) * 0.05,
        "w2": jnp.asarray(rng.standard_normal((E, f, d)), jnp.float32) * 0.05,
    }
    x = jnp.asarray(rng.standard_normal((4, 8, d)), jnp.float32)
    y, aux = jax.jit(lambda x, p: moe_ffn(x, p, cfg, ctx))(x, params)
    return np.asarray(y), int(aux["dropped"])


def test_moe_fp32_bit_identical_across_dispatch_methods(rng, monkeypatch):
    """End-to-end pin: the full fp32 MoE layer (routing -> dispatch -> a2a ->
    expert FFN -> combine) is bit-identical under both dispatch methods,
    with ample capacity AND under capacity overflow (dropped tokens)."""
    for cf in (8.0, 0.5):
        monkeypatch.setattr(grouping, "DEFAULT_DISPATCH_METHOD", "argsort")
        y_ref, drop_ref = _moe_smoke(np.random.default_rng(7), cf)
        monkeypatch.setattr(grouping, "DEFAULT_DISPATCH_METHOD", "counting")
        y_new, drop_new = _moe_smoke(np.random.default_rng(7), cf)
        np.testing.assert_array_equal(y_ref, y_new)
        assert drop_ref == drop_new
        if cf == 0.5:
            assert drop_new > 0    # the overflow config actually overflows
