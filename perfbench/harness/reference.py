"""The plain reference, the comparison that decides `correct`, and its
control.

The reference is `np.sort` of the keys the run generated: it imports
nothing of the program. The comparison is exact: a served or returned
sort must equal the reference key for key, so its limit is 0.

The control is the sort a later change might be tempted into: ordering
int32 keys by their top 24 bits only (what a float32 key, or a radix sort
that skips its last pass, would give). It breaks the configuration's one
guarantee, an exact total order, and the comparison has to catch it.
"""
from __future__ import annotations

import numpy as np

CONTROL_DROP_BITS = 8


def mismatched_keys(got, want) -> int:
    """Positions at which `got` differs from `want`, plus the keys one of
    them has beyond the other's length."""
    got = np.asarray(got).reshape(-1)
    want = np.asarray(want).reshape(-1)
    m = min(got.size, want.size)
    return int(np.count_nonzero(got[:m] != want[:m])) + abs(got.size - want.size)


def reference_sort(keys) -> np.ndarray:
    # integer keys that compare equal are the same value, so any sort
    # gives the one answer; NumPy's default kind is the fastest by far
    return np.sort(np.asarray(keys))


def control_sort(keys) -> np.ndarray:
    """The control, on the default device: a stable sort by key >> 8."""
    import jax
    import jax.numpy as jnp

    def by_top_bits(k):
        return jax.lax.sort((k >> CONTROL_DROP_BITS, k), num_keys=1,
                            is_stable=True)[1]

    return np.asarray(jax.jit(by_top_bits)(jnp.asarray(keys)))


class Comparison:
    """The numbers a run compares, each with its limit."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.items.append((name, value, limit))

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(v <= lim for _, v, lim in self.items)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}

    def lines(self) -> list[str]:
        return [f"check {n} = {v} (limit {lim})" for n, v, lim in self.items]
