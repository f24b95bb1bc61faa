"""Partitioner registry: each sort algorithm as a splitter strategy.

The paper's observation (HSS Secs. 3-4; also Axtmann et al.'s AMS framing)
is that Sample sort, AMS, and HSS share one three-phase skeleton — local
sort, splitter determination, exchange — and differ ONLY in how the p-1
splitters are determined. The registry makes that literal: an algorithm is
a `Partitioner` whose `splitters(local_sorted, ctx)` runs shard_map-resident
and returns the splitter keys; the surrounding skeleton (`sharded_sort`) and
the host driver (repro.sort.driver) are shared.

Multi-stage HSS is the one exception: it runs two nested exchanges, so it
overrides the whole shard-level pipeline (`sharded`) instead of just
`splitters`, and asks the driver for a 2-D mesh via `mesh_axes`.

Third-party strategies plug in with `register_partitioner`:

    @register_partitioner("mybisect")
    class MyPartitioner:
        def splitters(self, local_sorted, ctx): ...
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.analysis.contracts import CommsContract, register_contract
from repro.core.ams import ams_splitters
from repro.core.common import RECORD_KEY_WORDS
from repro.core.exchange import exchange, exchange_records
from repro.core.multistage import two_stage_sort_sharded
from repro.core.sample_sort import (
    default_regular_s, default_total_sample, random_sample_splitters,
    regular_sample_splitters)
from repro.core.splitters import (
    ROUND_COLLECTIVES, SplitterStats, hss_splitters, hss_splitters_lex)
from repro.kernels import dispatch
from repro.runtime import spans
from repro.sort.driver import factor_stages
from repro.sort.spec import SortSpec


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Everything a partitioner sees inside shard_map, besides the keys."""

    spec: SortSpec
    axis_names: tuple      # mesh axes of this sort, outermost first
    sizes: tuple           # per-axis shard counts
    rng: Any               # per-shard PRNG key
    initial_probes: Any = None

    @property
    def p(self) -> int:
        return int(math.prod(self.sizes))

    @property
    def axis_name(self) -> str:
        return self.axis_names[0]

    @property
    def hss_cfg(self):
        return self.spec.hss_config()

    @property
    def ex_cfg(self):
        return self.spec.exchange_config()


def null_stats(n_satisfied=None) -> SplitterStats:
    """Placeholder stats for algorithms without per-round diagnostics."""
    z = jnp.zeros((1,), jnp.int32)
    sat = z if n_satisfied is None else jnp.asarray(n_satisfied, jnp.int32)[None]
    return SplitterStats(gamma_size=z, sample_count=z, overflow=z,
                         n_satisfied=sat, rounds_used=jnp.int32(1))


class Partitioner:
    """Base strategy. Subclasses implement `splitters`; the standard
    shard-level pipeline (`sharded`) and mesh shape come for free."""

    name: str = "?"

    def mesh_axes(self, spec: SortSpec, p: int):
        """((axis_name, size), ...) this algorithm wants the driver to use."""
        return ((spec.axis_name, p),)

    def splitters(self, local_sorted, ctx: ShardCtx):
        """-> (splitter_keys (p-1,), splitter_ranks (p-1,), overflow, stats)."""
        raise NotImplementedError

    def sharded(self, local, rng, ctx: ShardCtx):
        """Full shard-level sort: local sort -> splitters -> exchange, each
        phase under its scope (`repro.runtime.spans`). A (words, n_local)
        block is records (`sharded_records`)."""
        if local.ndim == 2:
            return self.sharded_records(local, rng, ctx)
        sort_local = (ctx.spec.local_sort_fn
                      or dispatch.local_sort_fn(ctx.spec.kernel_policy))
        with spans.scope(spans.LOCAL_SORT):
            local_sorted = sort_local(local)
        with spans.scope(spans.SPLITTERS):
            keys, ranks, s_ovf, stats = self.splitters(
                local_sorted, dataclasses.replace(ctx, rng=rng))
        out, n_valid, e_ovf = exchange(
            local_sorted, keys, axis_name=ctx.axis_name, p=ctx.p,
            cfg=ctx.ex_cfg, eps=ctx.spec.eps)
        return out, n_valid, keys, ranks, s_ovf + e_ovf, stats

    def splitters_records(self, key_planes, ctx: ShardCtx):
        """`splitters` over word tuples: key_planes, the shard's sorted
        (n_local,) key words with the global index last -> ((p-1, words)
        keys, (p-1,) ranks, overflow, stats)."""
        raise NotImplementedError(
            f"partitioner {self.name!r} does not sort records")

    def sharded_records(self, local, rng, ctx: ShardCtx):
        """The shard-level sort of records: `local` is (RECORD_WORDS,
        n_local) uint32 word planes, big-endian, the key in the first
        RECORD_KEY_WORDS. The local sort orders them by those words, ties
        by position, so by each record's global index too; the splitters
        cut that order, the global index breaking ties; the dense exchange
        moves whole records and merges them. Stats are the splitters' and
        the exchange's (p, 2) slot counts."""
        kw, n_local = RECORD_KEY_WORDS, local.shape[1]
        with spans.scope(spans.LOCAL_SORT):
            records, order = dispatch.sort_planes(local, kw)
        me = jax.lax.axis_index(ctx.axis_name)
        gidx = (me * n_local + order).astype(jnp.uint32)
        key_planes = tuple(records[w] for w in range(kw)) + (gidx,)
        with spans.scope(spans.SPLITTERS):
            keys, ranks, s_ovf, stats = self.splitters_records(
                key_planes, dataclasses.replace(ctx, rng=rng))
        out, n_valid, e_ovf, sent = exchange_records(
            records, key_planes, keys, axis_name=ctx.axis_name, p=ctx.p,
            cfg=ctx.ex_cfg, eps=ctx.spec.eps)
        return out, n_valid, keys, ranks, s_ovf + e_ovf, (stats, sent)

    def partition_sorted(self, local_sorted, rng, ctx: ShardCtx, *,
                         n_valid=None, ex_cfg=None):
        """Splitters + exchange over an already-sorted shard — the relaxed
        seam the semisort light path rides (DESIGN.md Section 10). Unlike
        `sharded`, the caller owns the local sort and may mask a tail as
        hi-sentinel padding, passing the real count via `n_valid` so the
        exchange excludes the pad from the last destination slice. The
        splitter rounds see the sentinel tail as genuine max keys, which
        only biases the top splitters upward — grouping (not total order)
        is the contract here, so that is harmless."""
        with spans.scope(spans.SPLITTERS):
            keys, ranks, s_ovf, stats = self.splitters(
                local_sorted, dataclasses.replace(ctx, rng=rng))
        out, n_out, e_ovf = exchange(
            local_sorted, keys, axis_name=ctx.axis_name, p=ctx.p,
            cfg=ex_cfg if ex_cfg is not None else ctx.ex_cfg,
            eps=ctx.spec.eps, n_valid=n_valid)
        return out, n_out, keys, ranks, s_ovf + e_ovf, stats

_REGISTRY: dict[str, Partitioner] = {}


def register_partitioner(name: str):
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls
    return deco


def get_partitioner(name: str) -> Partitioner:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown sort algorithm {name!r}; available: "
            f"{sorted(_REGISTRY)}") from None


def available_algorithms() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# Splitter-phase wire contracts, one per algorithm, proven over
# `repro.analysis.programs.splitters_program` by the lint CLI. The full
# pipeline's expected totals are these plus the strategy's row in
# `repro.core.exchange.EXCHANGE_COLLECTIVES`. A splitter phase never
# exchanges payload, so every contract bans all_to_all outright.
_BATCH_INVARIANT = ("all_gather", "all_to_all", "psum", "ppermute")

register_contract("splitters:hss", CommsContract(
    name="splitters:hss",
    description="k-round histogram refinement: ONE sample all_gather and "
                "ONE fused rank/meta psum per round, converged rounds "
                "communication-free",
    total_counts={"all_gather": 1, "psum": 1, "all_to_all": 0},
    round_collectives=dict(ROUND_COLLECTIVES),
    converged_branch_pure=True,
    batch_invariant=_BATCH_INVARIANT))

register_contract("splitters:sample_random", CommsContract(
    name="splitters:sample_random",
    description="one Bernoulli sample all_gather + overflow/valid psums",
    total_counts={"all_gather": 1, "psum": 2, "all_to_all": 0},
    batch_invariant=_BATCH_INVARIANT))

register_contract("splitters:sample_regular", CommsContract(
    name="splitters:sample_regular",
    description="one regular-sample all_gather, fully deterministic",
    total_counts={"all_gather": 1, "psum": 0, "all_to_all": 0},
    batch_invariant=_BATCH_INVARIANT))

register_contract("splitters:ams", CommsContract(
    name="splitters:ams",
    description="one sample all_gather + overflow psum + ONE fused "
                "histogram psum (the single scanning round)",
    total_counts={"all_gather": 1, "psum": 2, "all_to_all": 0},
    batch_invariant=_BATCH_INVARIANT))


@register_partitioner("hss")
class HSSPartitioner(Partitioner):
    """Histogram Sort with Sampling (the paper's algorithm, Section 4)."""

    def splitters(self, local_sorted, ctx):
        keys, ranks, stats = hss_splitters(
            local_sorted, axis_name=ctx.axis_name, p=ctx.p, cfg=ctx.hss_cfg,
            rng=ctx.rng, initial_probes=ctx.initial_probes)
        return keys, ranks, jnp.zeros((), jnp.int32), stats

    def splitters_records(self, key_planes, ctx):
        keys, ranks, stats = hss_splitters_lex(
            key_planes, axis_name=ctx.axis_name, p=ctx.p, cfg=ctx.hss_cfg,
            rng=ctx.rng)
        return keys, ranks, jnp.zeros((), jnp.int32), stats

@register_partitioner("sample_random")
class RandomSamplePartitioner(Partitioner):
    """Random-sampling sample sort (Blelloch et al.; Theorem 3.1)."""

    def splitters(self, local_sorted, ctx):
        total = ctx.spec.total_sample or default_total_sample(
            ctx.p, local_sorted.shape[0], ctx.spec.eps)
        keys, ovf = random_sample_splitters(
            local_sorted, axis_name=ctx.axis_name, p=ctx.p,
            total_sample=total, rng=ctx.rng,
            kernel_policy=ctx.spec.kernel_policy)
        return keys, jnp.zeros_like(keys, jnp.int32), ovf, null_stats()

@register_partitioner("sample_regular")
class RegularSamplePartitioner(Partitioner):
    """Regular-sampling sample sort (PSRS; Theorem 3.2). Deterministic."""

    def splitters(self, local_sorted, ctx):
        s = ctx.spec.s or default_regular_s(ctx.p, ctx.spec.eps)
        keys = regular_sample_splitters(
            local_sorted, axis_name=ctx.axis_name, p=ctx.p, s=s,
            kernel_policy=ctx.spec.kernel_policy)
        return (keys, jnp.zeros_like(keys, jnp.int32),
                jnp.zeros((), jnp.int32), null_stats())

@register_partitioner("ams")
class AMSPartitioner(Partitioner):
    """Single-stage AMS scanning baseline (Section 3.6, Appendix A)."""

    def splitters(self, local_sorted, ctx):
        keys, ranks, ovf, ok = ams_splitters(
            local_sorted, axis_name=ctx.axis_name, p=ctx.p, rng=ctx.rng,
            eps=ctx.spec.eps, total_sample=ctx.spec.total_sample,
            kernel_policy=ctx.spec.kernel_policy)
        return keys, ranks, ovf, null_stats(
            jnp.where(ok, ctx.p - 1, 0))

#: Collectives of the two-stage pipeline *outside* its two exchanges: the
#: group-split and intra-group splitter phases plus group-size bookkeeping
#: psums. The lint's expected totals for a multistage program are this
#: base plus 2 x `EXCHANGE_COLLECTIVES[strategy]` (one exchange per
#: stage).
MULTISTAGE_BASE_COLLECTIVES = {"all_gather": 2, "psum": 7, "all_to_all": 0}


@register_partitioner("multistage")
class MultistagePartitioner(Partitioner):
    """Two-stage HSS (Sections 5.3/6.1): group split + intra-group sort."""

    def mesh_axes(self, spec: SortSpec, p: int):
        if spec.mesh is not None:   # honor the caller's (r1, r2) factoring
            return ((spec.outer_axis, spec.mesh.shape[spec.outer_axis]),
                    (spec.inner_axis, spec.mesh.shape[spec.inner_axis]))
        r1, r2 = factor_stages(p)
        return ((spec.outer_axis, r1), (spec.inner_axis, r2))

    def splitters(self, local_sorted, ctx):
        raise NotImplementedError("multistage overrides `sharded` directly")

    def sharded(self, local, rng, ctx):
        out, n_valid, ovf = two_stage_sort_sharded(
            local, outer_axis=ctx.axis_names[0], inner_axis=ctx.axis_names[1],
            r1=ctx.sizes[0], r2=ctx.sizes[1], rng=rng,
            hss_cfg=ctx.hss_cfg, ex_cfg=ctx.ex_cfg)
        m = jnp.zeros((0,), jnp.int32)
        return (out, n_valid, jnp.zeros((0,), local.dtype), m, ovf,
                null_stats())
