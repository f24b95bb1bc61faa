"""Probe-rank histogram Pallas kernel (the paper's histogramming hot spot).

rank[m] = #{local keys < probe[m]}. The paper does M binary searches per round
(O(M log n) scalar work); on TPU a tiled comparison reduction is faster for
the probe counts HSS produces (M = O(p) per round). Keys arrive as a
(rows, 128) tile per grid step; the probes arrive once, broadcast along
lanes to an (M, 128) block. Each key row (1, 128) is compared against that
block and the (M, 128) int32 accumulator — the output block, resident across
the key-tile grid axis — gains one count per lane; the wrapper sums the 128
lanes. O(n*M/1024) fully packed VPU ops with zero gather/scatter.

The keys need NOT be sorted — the kernel counts, it does not search. Sentinel
(+inf / int-max) padded keys never compare below a real probe, so capacity
padding is free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import spans

LANES = 128
SUBLANES = 8


def _probe_rank_kernel(keys_ref, probes_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = keys_ref.shape[1]
    group = min(SUBLANES, rows)
    probes = probes_ref[0]                      # (M, 128)

    def body(g, acc):
        start = pl.multiple_of(g * group, group)
        tile = keys_ref[0, pl.ds(start, group), :]   # (group, 128)
        for j in range(group):
            acc = acc + (tile[j:j + 1, :] < probes).astype(jnp.int32)
        return acc

    out_ref[0] = jax.lax.fori_loop(0, rows // group, body, out_ref[0])


def probe_ranks_pallas(keys: jax.Array, probes: jax.Array, *, tile: int,
                       interpret: bool) -> jax.Array:
    """Per-row probe ranks of a (B, n) key batch against (B, M) probes.

    n % tile == 0, tile % 128 == 0, M % 8 == 0. One launch over a
    (B, n // tile) grid: the key-tile axis iterates fastest, so each row's
    (M, 128) accumulator is revisited, and re-initialized when the tile
    index wraps to 0 for the next row. Returns (B, M) int32.
    """
    b, n = keys.shape
    m = probes.shape[1]
    assert probes.shape[0] == b, (keys.shape, probes.shape)
    rows = tile // LANES
    assert n % tile == 0 and tile % LANES == 0 and m % SUBLANES == 0, (
        n, tile, m)
    assert rows % min(SUBLANES, rows) == 0, tile
    keys3 = keys.reshape(b, n // LANES, LANES)
    probes3 = jnp.broadcast_to(probes[:, :, None], (b, m, LANES))
    counts = pl.pallas_call(
        _probe_rank_kernel,
        grid=(b, n // tile),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda r, i: (r, i, 0)),
            pl.BlockSpec((1, m, LANES), lambda r, i: (r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, m, LANES), lambda r, i: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, LANES), jnp.int32),
        interpret=interpret,
        name=spans.HISTOGRAM_KERNEL,
    )(keys3, probes3)
    return jnp.sum(counts, axis=2, dtype=jnp.int32)
