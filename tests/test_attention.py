"""Attention path equivalence: full einsum vs flash custom-VJP vs
context-parallel shard_map — values AND gradients must agree."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models.layers import (attention_chunked, attention_full,
                                 attention_seqpar)
from repro.parallel.ctx import ParallelCtx
from repro.parallel.compat import make_mesh

B, S, HQ, HKV, D = 2, 64, 6, 2, 16


def _qkv(rng, dtype=np.float32):
    q = jnp.asarray(rng.standard_normal((B, S, HQ, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_flash_matches_full(rng, causal, chunk):
    q, k, v = _qkv(rng)
    ref = attention_full(q, k, v, causal=causal)
    got = attention_chunked(q, k, v, causal=causal, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_grads_match_full(rng):
    q, k, v = _qkv(rng)

    def loss(fn, q, k, v):
        return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    gref = jax.grad(lambda *a: loss(
        lambda q, k, v: attention_full(q, k, v, causal=True), *a),
        argnums=(0, 1, 2))(q, k, v)
    gfla = jax.grad(lambda *a: loss(
        lambda q, k, v: attention_chunked(q, k, v, causal=True, chunk=16),
        *a), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gref, gfla):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-4)


def test_flash_sliding_window(rng):
    q, k, v = _qkv(rng)
    ref = attention_full(q, k, v, causal=True, window=24)
    got = attention_chunked(q, k, v, causal=True, chunk=8, window=24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_seqpar_matches_full(rng, causal):
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                      shard_heads=False)
    q, k, v = _qkv(rng)
    ref = attention_full(q, k, v, causal=causal)
    got = attention_seqpar(q, k, v, causal=causal, chunk=8, ctx=ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_seqpar_grads_match_full(rng):
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                      shard_heads=False)
    q, k, v = _qkv(rng)

    def loss(fn, q, k, v):
        return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    gref = jax.grad(lambda *a: loss(
        lambda q, k, v: attention_full(q, k, v, causal=True), *a),
        argnums=(0, 1, 2))(q, k, v)
    gsp = jax.jit(jax.grad(lambda *a: loss(
        lambda q, k, v: attention_seqpar(q, k, v, causal=True, chunk=8,
                                         ctx=ctx), *a),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gref, gsp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-4)


def test_gqa_grouping_semantics(rng):
    """GQA == full MHA with KV repeated per group."""
    q, k, v = _qkv(rng)
    ref = attention_full(q, jnp.repeat(k, HQ // HKV, 2),
                         jnp.repeat(v, HQ // HKV, 2), causal=True)
    got = attention_full(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
