"""jit'd wrappers for the probe-rank histogram kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.common import hi_sentinel, round_up
from repro.kernels import interpret_default as _interpret
from repro.kernels import to_kernel_keys
from repro.kernels.histogram.kernel import (LANES, SUBLANES,
                                            probe_ranks_pallas)

# Keys per grid step: a (64, 128) tile.
DEFAULT_TILE = 8192


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def probe_ranks(keys, probes, tile: int = DEFAULT_TILE,
                interpret: bool | None = None):
    """rank[m] = #{keys < probes[m]}; keys need not be sorted."""
    return probe_ranks_batched(keys[None], probes[None], tile=tile,
                               interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def probe_ranks_batched(keys, probes, tile: int = DEFAULT_TILE,
                        interpret: bool | None = None):
    """Per-row ranks: rank[b, m] = #{keys[b] < probes[b, m]}. One launch.

    Keys are sentinel-padded to whole (8, 128) tiles and to a multiple of
    the key tile (which shrinks to the row when the row is shorter), probes
    to a multiple of 8 (their ranks are sliced off)."""
    interpret = _interpret() if interpret is None else interpret
    keys, probes = to_kernel_keys(keys), to_kernel_keys(probes)
    b, n = keys.shape
    m = probes.shape[1]
    npad = round_up(max(n, 1), LANES * SUBLANES)
    t = min(tile, npad)
    npad = round_up(npad, t)
    keys = jnp.concatenate(
        [keys, jnp.full((b, npad - n), hi_sentinel(keys.dtype), keys.dtype)],
        axis=1)
    mpad = round_up(m, SUBLANES)
    probes = jnp.concatenate(
        [probes, jnp.zeros((b, mpad - m), probes.dtype)], axis=1)
    return probe_ranks_pallas(keys, probes, tile=t,
                              interpret=interpret)[:, :m]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def probe_counts(keys, probes, tile: int = DEFAULT_TILE,
                 interpret: bool | None = None):
    r = probe_ranks(keys, probes, tile=tile, interpret=interpret)
    n = jnp.int32(keys.shape[0])
    return jnp.diff(jnp.concatenate([jnp.zeros((1,), jnp.int32), r, n[None]]))
