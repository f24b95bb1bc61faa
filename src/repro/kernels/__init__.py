"""Pallas TPU kernels for the sort hot spots (DESIGN.md Sections 2.4-2.5).

bitonic_sort  VMEM-tiled bitonic sorting/merging networks — the local-sort
              phase the paper delegates to std::sort, rebuilt as
              data-independent compare-exchange networks that map onto the
              TPU VPU (no divergence, fully vectorized).
histogram     probe-count kernel — the per-round histogram: counts of local
              keys below each probe via tiled comparison reduction (an MXU/VPU
              arithmetic-intensity trade vs. scalar binary searches).
merge         k-way post-exchange merge — pairwise bitonic-merge tree over
              already-sorted runs (equal-capacity, contiguous, or ragged at
              traced offsets), with an HBM-resident strided pass above the
              VMEM budget so the cascade never falls back to an XLA sort.
dispatch      the backend/size-aware selection layer every core pipeline
              routes through: `kernel_policy` = "auto" | "pallas" | "xla".

Key contract (shared with repro.core.common): keys are NaN-free and never
equal the dtype's hi sentinel. The compare-exchange networks are built on
min/max, which propagate a float NaN into *both* lanes (destroying data
where jnp.sort would sort it last) — the `repro.sort` front-door's IEEE-754
bijection turns float keys into sortable ints before they reach the core,
and raw-core callers must do the same. Within that contract every kernel is
bit-identical to its XLA oracle.

Kernel bodies compare two key dtypes only, int32 and float32:
`to_kernel_keys` maps every narrower key dtype, and uint32, onto one of
them by an order-preserving bijection, and `from_kernel_keys` maps back.
"""
import jax
import jax.numpy as jnp

_SIGN = 0x80000000


def interpret_default() -> bool:
    """Whether Pallas kernels run in interpret mode by default: only a real
    TPU compiles Mosaic kernels. The single source of truth — dispatch and
    the per-kernel ops modules all resolve `interpret=None` through this."""
    return jax.default_backend() != "tpu"


def to_kernel_keys(x):
    """Order-preserving map of <=32-bit keys onto int32 or float32 (uint32
    by flipping the sign bit). 64-bit keys pass through unchanged: Mosaic
    has no 64-bit vectors, so only interpret mode can run them."""
    if x.dtype.itemsize == 8:
        return x
    if x.dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(x ^ jnp.uint32(_SIGN), jnp.int32)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.float32)
    return x.astype(jnp.int32)


def from_kernel_keys(y, dtype):
    """Inverse of `to_kernel_keys` for keys that were of `dtype`."""
    if y.dtype == dtype:
        return y
    if dtype == jnp.uint32:
        return jax.lax.bitcast_convert_type(y, jnp.uint32) ^ jnp.uint32(_SIGN)
    return y.astype(dtype)
