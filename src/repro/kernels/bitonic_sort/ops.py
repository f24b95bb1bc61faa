"""jit'd wrappers around the bitonic kernel.

`local_sort(x)` is the drop-in local-sort for the HSS pipeline (route it via
`repro.kernels.dispatch.local_sort`, or pass it as `local_sort_fn`): pad to a
power of two with the hi sentinel, kernel-sort VMEM blocks, then the merge
cascade (`repro.kernels.merge.ops.merge_cascade`). interpret=True on CPU
(kernel body executes in Python), compiled Mosaic on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.common import hi_sentinel, pow2_ceil
from repro.kernels import from_kernel_keys, to_kernel_keys
from repro.kernels import interpret_default as _interpret
from repro.kernels.bitonic_sort import kernel as K

# VMEM budget: a merge block of MAX_RUN keys (plus double buffering and the
# step temporaries) must fit VMEM; 64K keys = 256 KiB at 4 bytes. Beyond
# that, merge stages continue with the HBM-resident strided pass
# (kernels.merge.kernel.merge_level), so the cascade never leaves kernel
# land. DESIGN.md Section 2.5 has the math.
DEFAULT_BLOCK = 1024
MAX_RUN = 65536
# Rows are padded to at least one (8, 128) tile of 32-bit keys.
MIN_ROW = 8 * K.LANES


def _flip_odd_runs(x, run: int):
    """Reverse every odd run of length `run` along the last axis: ascending
    runs -> the alternating directions the network's merge stages take."""
    # A select over (pairs, 2, rows, lanes) tiles: slicing the pair axis
    # instead leaves size-1 dims that TPU layouts pad, and compiles in
    # minutes at shard scale.
    lanes = min(run, K.LANES)
    y = x.reshape(-1, 2, run // lanes, lanes)
    odd = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1) == 1
    return jnp.where(odd, jnp.flip(y, (2, 3)), y).reshape(x.shape)


def _sort_rows(x, block: int, interpret: bool):
    """Sort each row of a (B, n) array: one kernel launch per pass."""
    # deferred: merge.ops imports this module for its ragged-spill fallback
    from repro.kernels.merge.ops import merge_cascade

    b, n = x.shape
    dtype = x.dtype
    x = to_kernel_keys(x)
    row = pow2_ceil(max(n, MIN_ROW))
    blk = min(block, row)
    xp = jnp.concatenate(
        [x, jnp.full((b, row - n), hi_sentinel(x.dtype), x.dtype)], axis=1)
    flat = K.network_pass(xp.reshape(-1), block=blk, steps=K.stages(2, blk),
                          row_len=row, interpret=interpret)
    flat = merge_cascade(flat, blk, vmem_block=MAX_RUN, row_len=row,
                         interpret=interpret)
    return from_kernel_keys(flat.reshape(b, row)[:, :n], dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_sort(x, block: int = DEFAULT_BLOCK, interpret: bool | None = None):
    """Sort independent `block`-sized runs ascending (power-of-two block)."""
    interpret = _interpret() if interpret is None else interpret
    y = K.network_pass(to_kernel_keys(x), block=block,
                       steps=K.stages(2, block), row_len=block,
                       interpret=interpret)
    return from_kernel_keys(y, x.dtype)


@functools.partial(jax.jit, static_argnames=("run", "interpret"))
def merge_pass(x, run: int, interpret: bool | None = None):
    """Merge adjacent ascending runs of length `run` into runs of 2*run."""
    interpret = _interpret() if interpret is None else interpret
    y = _flip_odd_runs(to_kernel_keys(x), run)
    y = K.network_pass(y, block=2 * run, steps=K.stages(2 * run, 2 * run),
                       row_len=2 * run, interpret=interpret)
    return from_kernel_keys(y, x.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def local_sort(x, block: int = DEFAULT_BLOCK, interpret: bool | None = None):
    """Full local sort: kernel block sort + kernel merge cascade."""
    interpret = _interpret() if interpret is None else interpret
    return _sort_rows(x[None], block, interpret)[0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def local_sort_batched(x, block: int = DEFAULT_BLOCK,
                       interpret: bool | None = None):
    """Sort each row of a (B, n) array in one kernel launch per pass.

    Rows are sentinel-padded to a shared power-of-two length and laid end
    to end; the network's directions come from the index within the row,
    so every pass (VMEM block or HBM strided step) stays within its row.
    B rows therefore cost the *same number of kernel launches* as one row.
    """
    interpret = _interpret() if interpret is None else interpret
    return _sort_rows(x, block, interpret)
