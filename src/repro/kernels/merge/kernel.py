"""HBM-resident bitonic merge Pallas kernel.

Merge stage k of the bitonic network (see `bitonic_sort.kernel`) runs
compare-exchange steps at distances k/2, ..., 1. `network_pass` runs steps
whose distance is below its VMEM block; this module runs the rest as
strided passes so the pair of runs can stay in HBM:

  strided_compare_exchange  one step at distance d: the flat array viewed
                            as (n/2d, 2, d/lanes, lanes), where [i, 0] and
                            [i, 1] are the lo/hi elements d apart. Each
                            grid step loads a (2, cols) pair of tiles and
                            writes min/max in the direction of stage k.
                            O(n) HBM traffic per step, O(cols) VMEM.
  merge_level               the whole stage: strided steps while
                            d >= vmem_block, then one `network_pass` over
                            vmem_block-sized blocks for distances below.

Correctness is a property of the comparator network, not of the chunking:
these shapes execute exactly the comparators of the standard bitonic merge,
in network order, so the result is bit-identical to the VMEM kernel and to
the `jnp.sort` oracle for any block sizes.

The strided launch is named `<family>_strided` (repro.runtime.spans), the
caller's family as for `network_pass`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitonic_sort.kernel import LANES, network_pass
from repro.runtime import spans

# Keys per half-tile of a strided HBM pass: a (2, 8, 128) pair of tiles
# (8 KiB at 4 bytes) per grid step — deliberately tiny so the pass
# coexists with whatever else the surrounding program keeps resident.
DEFAULT_COLS = 1024


def _strided_ce_kernel(x_ref, o_ref, *, d: int, k: int, row_len: int):
    lo, hi = x_ref[0, 0], x_ref[0, 1]   # elements d apart
    mn, mx = jnp.minimum(lo, hi), jnp.maximum(lo, hi)
    # the pair's index within its row decides the direction of stage k
    asc = (((pl.program_id(0) * 2 * d) & (row_len - 1)) & k) == 0

    @pl.when(asc)
    def _ascending():
        o_ref[0, 0] = mn
        o_ref[0, 1] = mx

    @pl.when(jnp.logical_not(asc))
    def _descending():
        o_ref[0, 0] = mx
        o_ref[0, 1] = mn


def strided_compare_exchange(x: jax.Array, d: int, k: int, *, cols: int,
                             row_len: int, interpret: bool,
                             family: str = spans.MERGE_FAMILY) -> jax.Array:
    """Step (k, d) of the network over the flat array x (d % cols == 0)."""
    n = x.shape[0]
    assert n % (2 * d) == 0 and d % cols == 0, (n, d, cols)
    lanes = min(cols, LANES)
    x4 = x.reshape(n // (2 * d), 2, d // lanes, lanes)
    tile = (1, 2, cols // lanes, lanes)
    out = pl.pallas_call(
        functools.partial(_strided_ce_kernel, d=d, k=k, row_len=row_len),
        grid=(n // (2 * d), d // cols),
        in_specs=[pl.BlockSpec(tile, lambda i, j: (i, 0, j, 0))],
        out_specs=pl.BlockSpec(tile, lambda i, j: (i, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct(x4.shape, x.dtype),
        interpret=interpret,
        name=f"{family}_{spans.STRIDED}",
    )(x4)
    return out.reshape(n)


def merge_level(x: jax.Array, run: int, *, vmem_block: int, row_len: int,
                cols: int = DEFAULT_COLS, interpret: bool,
                family: str = spans.MERGE_FAMILY) -> jax.Array:
    """Merge stage k = 2*run: runs of length `run` in alternating directions
    -> runs of 2*run, holding at most `vmem_block` keys in VMEM."""
    k = 2 * run
    d = run
    while d >= vmem_block:
        x = strided_compare_exchange(x, d, k, cols=min(d, cols),
                                     row_len=row_len, interpret=interpret,
                                     family=family)
        d //= 2
    steps = []
    while d >= 1:
        steps.append((k, d))
        d //= 2
    return network_pass(x, block=2 * steps[0][1], steps=tuple(steps),
                        row_len=row_len, interpret=interpret, family=family)
