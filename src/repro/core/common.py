"""Shared utilities for the HSS core: sentinels, dtype helpers, small math.

Keys flowing through the partitioner are 1-D arrays of a numeric dtype. XLA
requires static shapes, so "absent" slots in sample buffers / exchange buffers
are filled with the dtype's +sentinel (greater than any real key). Callers must
not feed sentinel-valued keys; `repro.core.tagging` produces tag-packed keys
that stay strictly below the sentinel.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


#: Sort Benchmark records (repro.sort.sort_records): 100 bytes as 25 uint32
#: words, big-endian, so unsigned word order is byte (memcmp) order; the
#: 10-byte key fills words 0, 1 and the top half of word 2. A sort compares
#: the first 3 words whole, so records with equal keys come out ordered by
#: bytes 10 and 11, which any order of equal keys allows.
RECORD_BYTES = 100
RECORD_WORDS = 25
RECORD_KEY_WORDS = 3


def hi_sentinel(dtype) -> Any:
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def lo_sentinel(dtype) -> Any:
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pow2_ceil(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def auto_rounds(p: int, eps: float) -> int:
    """Optimal round count k = log(log p / eps) (Theorem 4.8), at least 1."""
    if p <= 1:
        return 1
    return max(1, round(math.log(max(math.e, 2.0 * math.log(p) / eps))))


def final_sampling_ratio(p: int, eps: float) -> float:
    """s_k = 2 ln p / eps (Lemma 4.3): sampling ratio that pins every splitter."""
    return 2.0 * math.log(max(p, 2)) / eps


@dataclasses.dataclass(frozen=True)
class HSSConfig:
    """Configuration of the HSS splitter-determination stage.

    eps:
        load-balance slack: every output shard holds <= (1+eps) * N/p keys and
        splitter ranks land within the target range T_i (globally balanced).
    rounds:
        number of sampling+histogramming rounds k. 0 => auto_rounds(p, eps).
    sample_per_shard:
        per-shard per-round sample-buffer capacity ("f" in the paper's Table 4,
        overall sample ~= f*p per round). 0 => auto-sized from theory with
        Chernoff slack.
    adaptive:
        True (paper's implementation, Section 6.2): per-round Bernoulli
        probability is chosen as target_sample / |gamma_j| so the expected
        sample per round is constant. False (paper's analysis, Theorem 4.7):
        fixed ratios s_j = (2 ln p / eps)^{j/k}.
    out_slack:
        output-buffer slack multiplier on (1+eps)*N/p for the exchanged shard.
    capacity_scale:
        uniform multiplier on every statically-sized buffer (sample caps
        here; pair/out caps in ExchangeConfig). 1.0 in steady state; the
        overflow-retry policy (SortSpec.on_overflow="retry") re-launches
        with 2^k so one knob relieves every overflow source at once.
    kernel_policy:
        compute-backend selection for the local sort, sample sorts, and
        probe ranking: "auto" (Pallas kernels on TPU, XLA elsewhere),
        "pallas", or "xla" (repro.kernels.dispatch, DESIGN.md Section 2.5).
    """

    eps: float = 0.05
    rounds: int = 0
    sample_per_shard: int = 0
    adaptive: bool = True
    out_slack: float = 1.0
    capacity_scale: float = 1.0
    kernel_policy: str = "auto"

    def resolved_rounds(self, p: int) -> int:
        return self.rounds if self.rounds > 0 else auto_rounds(p, self.eps)

    def resolved_sample_cap(self, p: int) -> int:
        if self.sample_per_shard > 0:
            cap = self.sample_per_shard
        else:
            k = self.resolved_rounds(p)
            ratio = final_sampling_ratio(p, self.eps) ** (1.0 / k)
            # Expected per-shard sample per round is ~ratio (round 1) and
            # <= 4*ratio later rounds (Lemma 4.6, constants incl.); x2 slack.
            cap = int(round_up(max(8, math.ceil(8.0 * ratio)), 8))
        if self.capacity_scale != 1.0:
            cap = int(round_up(max(8, int(cap * self.capacity_scale)), 8))
        return cap


def sampling_ratios(p: int, eps: float, k: int) -> np.ndarray:
    """Theory schedule s_j = (2 ln p / eps)^{j/k}, j = 1..k (Theorem 4.7)."""
    s_k = final_sampling_ratio(p, eps)
    return np.array([s_k ** ((j + 1) / k) for j in range(k)], dtype=np.float64)


def interval_union_size(lo_rank, hi_rank):
    """Size of the union of splitter intervals [lo_i, hi_i] in rank space.

    Intervals are monotone (lo and hi nondecreasing in i), so the union is
    sum_i max(0, hi_i - max(lo_i, cummax(hi)_{i-1})). Works for both jnp and np.
    """
    if isinstance(lo_rank, jax.Array) or isinstance(hi_rank, jax.Array):
        cummax = jax.lax.cummax(hi_rank)
        cummax_prev = jnp.concatenate([lo_rank[:1], cummax[:-1]])
        return jnp.sum(jnp.maximum(hi_rank - jnp.maximum(lo_rank, cummax_prev), 0))
    cummax = np.maximum.accumulate(hi_rank)
    cummax_prev = np.concatenate([lo_rank[:1], cummax[:-1]])
    return np.sum(np.maximum(hi_rank - np.maximum(lo_rank, cummax_prev), 0))
