"""Bitonic sorting-network Pallas kernel.

Sorting networks are the TPU-native local sort: compare-exchange distances
are static, control flow is data-independent (every step is a full-width
vector min/max), and blocks stream HBM -> VMEM tile by tile via BlockSpec.

Network. The bitonic sort of a row of L keys runs merge stages
k = 2, 4, ..., L; stage k runs compare-exchange steps at distances
d = k/2, ..., 1, and the pair (i, i+d) is ordered ascending iff
(i & k) == 0. After stage k the row is a sequence of sorted runs of length
k whose directions alternate (run j ascending iff j is even), which is
exactly the bitonic input stage 2k needs — so the cascade of merges that
follows a block sort needs no reversal. The last stage (k = L) orders the
whole row ascending. `network_pass` runs any contiguous slice of those
steps over independent VMEM blocks; `repro.kernels.merge` runs the steps
whose distance exceeds a block as strided HBM passes.

Layout. A flat key array is viewed as (n / lanes, lanes), lanes =
min(block, 128), so a block of B >= 1024 keys is a (B/128, 128) tile
stack. The partner at distance d is read with `pltpu.roll`: along lanes
for d < lanes, along sublanes (rows) above. Each element selects the
rolled copy holding its partner (i+d for the low element of a pair, i-d
for the high one) and keeps the min or the max.

Rows. Several independent rows of `row_len` keys (a power of two) may
share one flat array — a request batch. Directions are computed from the
index within the row, so the last stage orders each row ascending and no
comparator crosses a row boundary as long as blocks divide rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def stages(k_from: int, k_to: int) -> tuple:
    """The (k, d) compare-exchange steps of merge stages k_from .. k_to."""
    steps = []
    k = k_from
    while k <= k_to:
        d = k // 2
        while d >= 1:
            steps.append((k, d))
            d //= 2
        k *= 2
    return tuple(steps)


def _compare_exchange(x, pos, idx, k: int, d: int):
    """One network step over a (rows, lanes) block: pos is the index within
    the block, idx the index within the row (direction bit k)."""
    rows, lanes = x.shape
    if d < lanes:
        axis, shift, size = 1, d, lanes
    else:
        axis, shift, size = 0, d // lanes, rows
    up = pltpu.roll(x, size - shift, axis)     # x[i + d]
    down = pltpu.roll(x, shift, axis)          # x[i - d]
    low = (pos & d) == 0
    partner = jnp.where(low, up, down)
    keep_min = low == ((idx & k) == 0)
    return jnp.where(keep_min, jnp.minimum(x, partner),
                     jnp.maximum(x, partner))


def _network_kernel(x_ref, o_ref, *, steps, row_len):
    rows, lanes = x_ref.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    pos = r * lanes + c
    idx = (pl.program_id(0) * (rows * lanes) + pos) & (row_len - 1)
    x = x_ref[...]
    for k, d in steps:
        x = _compare_exchange(x, pos, idx, k, d)
    o_ref[...] = x


def network_pass(x: jax.Array, *, block: int, steps: tuple, row_len: int,
                 interpret: bool) -> jax.Array:
    """Run `steps` on every aligned `block` of the flat array x, whose
    rows hold `row_len` keys. Every step's distance must be < block."""
    n = x.shape[0]
    assert n % block == 0 and row_len % block == 0, (n, block, row_len)
    assert all(d < block for _, d in steps), (block, steps)
    lanes = min(block, LANES)
    tile = (block // lanes, lanes)
    out = pl.pallas_call(
        functools.partial(_network_kernel, steps=steps, row_len=row_len),
        grid=(n // block,),
        in_specs=[pl.BlockSpec(tile, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(tile, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // lanes, lanes), x.dtype),
        interpret=interpret,
    )(x.reshape(n // lanes, lanes))
    return out.reshape(n)
