"""Perf-lever paths: fp8 gather, fp8 a2a wire, ring KV cache, and
weights-stationary MoE decode must preserve semantics on a real mesh."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models.moe import moe_ffn
from repro.parallel.ctx import ParallelCtx
from repro.parallel.compat import make_mesh


def _moe_setup(rng, cfg):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    params = {
        "router": jnp.asarray(rng.standard_normal((d, E)), jnp.float32) * 0.1,
        "w1": jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32) * 0.05,
        "w3": jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32) * 0.05,
        "w2": jnp.asarray(rng.standard_normal((E, f, d)), jnp.float32) * 0.05,
    }
    return params


def _ctx(p=8):
    mesh = make_mesh((2, 4), ("data", "model"))
    return ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")


def test_moe_decode_weights_stationary_matches_big_path(rng):
    """Small-T (weights-stationary decode) == big-T (a2a) routing semantics."""
    cfg = dataclasses.replace(smoke_config("phi3.5-moe-42b-a6.6b"),
                              n_experts=8, d_model=64, d_ff_expert=96,
                              moe_capacity_factor=8.0)
    ctx = _ctx()
    params = _moe_setup(rng, cfg)
    x = jnp.asarray(rng.standard_normal((4, 8, cfg.d_model)), jnp.float32)

    # big path needs s % tp == 0 and s >= tp => (4, 8) with tp=4 qualifies
    y_big, aux_big = jax.jit(lambda x, p: moe_ffn(x, p, cfg, ctx))(x, params)
    # decode shape: one token per sequence -> small-T path
    y_small = []
    for t in range(x.shape[1]):
        ys, aux_s = jax.jit(lambda xt, p: moe_ffn(xt, p, cfg, ctx))(
            x[:, t:t + 1], params)
        y_small.append(ys)
    y_small = jnp.concatenate(y_small, axis=1)
    assert int(aux_big["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y_small), np.asarray(y_big),
                               rtol=2e-3, atol=2e-3)


def test_moe_fp8_wire_close_to_bf16(rng):
    """fp8 gather+a2a wire stays within quantization tolerance of exact."""
    cfg = dataclasses.replace(smoke_config("phi3.5-moe-42b-a6.6b"),
                              n_experts=8, d_model=64, d_ff_expert=96,
                              moe_capacity_factor=8.0)
    cfg8 = dataclasses.replace(cfg, moe_gather_dtype="float8_e4m3fn",
                               moe_a2a_dtype="float8_e4m3fn")
    ctx = _ctx()
    params = _moe_setup(rng, cfg)
    x = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)) * 0.5, jnp.float32)
    y, _ = jax.jit(lambda x, p: moe_ffn(x, p, cfg, ctx))(x, params)
    y8, _ = jax.jit(lambda x, p: moe_ffn(x, p, cfg8, ctx))(x, params)
    err = np.abs(np.asarray(y8) - np.asarray(y))
    ref = np.abs(np.asarray(y)).mean() + 1e-6
    assert err.mean() / ref < 0.25     # e4m3 ~6% relative per value
    assert np.isfinite(np.asarray(y8)).all()


def test_ring_cache_decode_matches_forward_past_window(rng):
    """zamba2 ring cache: decode beyond the window still matches the
    windowed teacher-forced forward (cache wraps around)."""
    from repro.models.lm import forward
    from repro.models.params import init_params
    from repro.models.steps import make_prefill_step, make_serve_step
    from repro.parallel import local_ctx
    cfg = smoke_config("zamba2-1.2b")  # attn_window = 16 in smoke
    ctx = local_ctx()
    params = init_params(cfg, jax.random.key(0))
    S = 48  # 3x the window
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(2, S)), jnp.int32)
    logits_all, _, _ = jax.jit(lambda p, t: forward(p, t, cfg, ctx))(params, toks)

    prefill = jax.jit(make_prefill_step(cfg, ctx, S + 4))
    serve = jax.jit(make_serve_step(cfg, ctx))
    s0 = 32  # multiple of the window
    last, cache = prefill(params, {"tokens": toks[:, :s0]})
    np.testing.assert_allclose(np.asarray(last, np.float32),
                               np.asarray(logits_all[:, s0 - 1], np.float32),
                               rtol=0.15, atol=0.15)
    for t in range(s0, s0 + 6):     # decode across a ring wrap
        logits, cache = serve(params, cache, toks[:, t:t + 1], t)
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(logits_all[:, t], np.float32), rtol=0.15, atol=0.15)
    # cache really is O(window), not O(context)
    kshape = cache["shared_kv"][0].shape
    assert kshape[2] == cfg.attn_window
