"""Optimizer / checkpoint / fault-tolerance / data-pipeline tests."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.optim import (clip_by_global_norm, error_feedback_int8, global_norm,
                         init_compressor, make_optimizer)
from repro.optim.schedule import cosine_schedule
from repro.parallel.compat import make_mesh


def _tiny_params(rng):
    return {"a": {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)},
            "b": jnp.asarray(rng.standard_normal((16,)), jnp.float32)}


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_minimizes_quadratic(rng, name):
    opt = make_optimizer(name, weight_decay=0.0)
    params = _tiny_params(rng)
    target = jax.tree.map(lambda p: jnp.ones_like(p), params)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(
            lambda p: sum(jnp.sum((x - t) ** 2) for x, t in
                          zip(jax.tree.leaves(p), jax.tree.leaves(target))))(params)
        params, state = opt.update(grads, state, params, 0.05)
        return params, state, loss

    losses = []
    for _ in range(60):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < 0.05 * losses[0]


def test_adafactor_state_is_factored(rng):
    opt = make_optimizer("adafactor")
    params = {"w": jnp.zeros((32, 64))}
    state = opt.init(params)
    assert state["v"]["w"]["r"].shape == (32,)
    assert state["v"]["w"]["c"].shape == (64,)
    # memory: factored 2nd moment is O(n+m), not O(n*m)
    total_v = sum(x.size for x in jax.tree.leaves(state["v"]))
    assert total_v == 32 + 64


def test_clip_by_global_norm(rng):
    g = {"w": jnp.full((10,), 100.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 100
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1e-3) < 1e-9
    assert float(lr(100)) < float(lr(50)) < float(lr(10))


def test_error_feedback_compression_converges(rng):
    """Error feedback: quantization bias cancels over steps (sum of compressed
    grads tracks sum of true grads)."""
    g = {"w": jnp.asarray(rng.standard_normal((256,)), jnp.float32)}
    state = init_compressor(g)
    acc_true = np.zeros(256)
    acc_comp = np.zeros(256)
    for i in range(20):
        gi = {"w": g["w"] * (1 + 0.01 * i)}
        comp, state = error_feedback_int8(gi, state)
        acc_true += np.asarray(gi["w"])
        acc_comp += np.asarray(comp["w"])
    # residual bounded by one quantization step, not accumulated
    resid = np.abs(acc_true - acc_comp).max()
    assert resid < np.abs(g["w"]).max() / 127 * 2


# ---------------------------------------------------------------- checkpoint
def test_checkpoint_save_restore_roundtrip(tmp_path, rng):
    from repro.ckpt import latest_step, restore, save
    tree = _tiny_params(rng)
    save(str(tmp_path), 10, tree, extra={"next_step": 10})
    save(str(tmp_path), 20, tree, extra={"next_step": 20})
    assert latest_step(str(tmp_path)) == 20
    got, extra = restore(str(tmp_path), 20, jax.tree.map(jnp.zeros_like, tree))
    assert extra["next_step"] == 20
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_latest(tmp_path, rng):
    from repro.ckpt import latest_steps, save
    tree = _tiny_params(rng)
    for s in (1, 2, 3, 4, 5):
        save(str(tmp_path), s, tree, keep=2)
    assert latest_steps(str(tmp_path)) == [4, 5]


def test_checkpoint_reshard_restore(tmp_path, rng):
    """Elastic restore: save unsharded, restore onto a 4-device mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.ckpt import restore, save
    tree = {"w": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)}
    save(str(tmp_path), 1, tree)
    mesh = make_mesh((4,), ("d",), devices=jax.devices()[:4])
    sh = {"w": NamedSharding(mesh, P("d", None))}
    got, _ = restore(str(tmp_path), 1, tree, shardings=sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))


def test_async_checkpointer(tmp_path, rng):
    from repro.ckpt import AsyncCheckpointer, latest_step
    ck = AsyncCheckpointer(str(tmp_path))
    tree = _tiny_params(rng)
    ck.save(5, tree)
    ck.wait()
    assert latest_step(str(tmp_path)) == 5


# ------------------------------------------------------------- fault tolerance
def test_supervisor_restarts_from_checkpoint(tmp_path):
    from repro.runtime.ft import TrainSupervisor
    sup = TrainSupervisor(str(tmp_path), save_every=2, max_restarts=2,
                          async_save=False)
    crashed = {"done": False}

    def step_fn(step, state):
        if step == 5 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("injected node failure")
        return {"x": state["x"] + 1}, {"loss": 0.0}

    final = sup.run({"x": jnp.zeros(())}, 8, step_fn)
    assert sup.restarts == 1
    assert float(final["x"]) == 8  # every step executed exactly once post-restore


def test_step_timer_flags_stragglers():
    from repro.runtime.ft import StepTimer
    t = StepTimer(threshold=2.0)
    assert not t.record(1.0)
    for _ in range(5):
        assert not t.record(1.0)
    assert t.record(10.0)   # straggler
    assert t.stragglers == 1


# -------------------------------------------------------------------- data
def test_synthetic_data_deterministic():
    from repro.data.synthetic import SyntheticTokens
    d = SyntheticTokens(vocab=128, seq_len=16, global_batch=4, seed=1)
    a1, b1 = d.batch(7)
    a2, b2 = d.batch(7)
    np.testing.assert_array_equal(a1, a2)
    assert a1.shape == (4, 16) and b1.shape == (4, 16)
    assert a1.max() < 128
    # labels are next-token shifted
    np.testing.assert_array_equal(a1[:, 1:], b1[:, :-1])


def test_hss_length_bucketing(rng):
    from repro.data.partition import (bucket_lengths, pack_documents,
                                      padding_fraction)
    lengths = rng.lognormal(5.0, 1.0, size=4096).clip(16, 2048).astype(np.int32)
    shards, counts = bucket_lengths(lengths, n_shards=8)
    all_ids = np.sort(np.concatenate(shards))
    np.testing.assert_array_equal(all_ids, np.arange(4096))  # exact partition
    # contiguous length ranges: max length of shard i <= min of shard i+1
    for i in range(7):
        if shards[i].size and shards[i + 1].size:
            assert lengths[shards[i]].max() <= lengths[shards[i + 1]].min()
    # bucketed packing wastes less padding than random-order packing
    seq = 2048
    bucketed = sum((pack_documents(s, lengths, seq) for s in shards), [])
    rand = pack_documents(rng.permutation(4096), lengths, seq)
    assert padding_fraction(bucketed, lengths, seq) <= \
        padding_fraction(rand, lengths, seq) + 0.02
