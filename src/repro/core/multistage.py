"""Multi-stage HSS (paper Sections 5.3, 6.1).

Stage 1 partitions keys across r1 *groups* (the outer mesh axis) using HSS
splitter determination over the full machine; stage 2 sorts within each group
along the inner axis. This is the paper's node-level two-phase optimization
expressed as nested mesh axes: the stage-1 histogram has only r1-1 splitters
(cheaper), and stage-2 traffic stays inside a group (intra-node / intra-pod).

Generalizes hss_splitters via num_parts != num_shards and a traced n_valid
(stage-2 shards hold sentinel-padded ragged loads after the stage-1 exchange).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import jax.random as jr

from repro.core.common import HSSConfig, hi_sentinel
from repro.kernels import dispatch
from repro.core.exchange import ExchangeConfig, exchange
from repro.runtime import spans
from repro.core.splitters import (
    SplitterState, choose_splitters, refine, active_union_size, _sample_round,
)


def hss_splitters_general(
    local_sorted, *, axis_names, num_shards, num_parts, cfg: HSSConfig,
    rng, n_valid=None):
    """HSS splitter determination decoupled from the shard/part counts.

    axis_names: str or tuple of axis names the shards span (collectives run
      over all of them). num_shards: product of those axis sizes.
    num_parts: how many output parts to split into (num_parts-1 splitters).
    n_valid: traced count of real (non-sentinel) keys; default all.
    """
    n_local = local_sorted.shape[0]
    n = n_valid if n_valid is not None else n_local * num_shards
    n = jnp.asarray(n, jnp.int32)
    dtype = local_sorted.dtype
    k = cfg.resolved_rounds(num_parts)
    cap = cfg.resolved_sample_cap(num_parts)
    tol = jnp.maximum(1, (n.astype(jnp.float32) * cfg.eps / (2 * num_parts)).astype(jnp.int32))
    targets = (jnp.arange(1, num_parts, dtype=jnp.int32)
               * n // num_parts).astype(jnp.int32)
    f_total = float(cap * num_shards) / 2.0

    m = num_parts - 1
    state0 = SplitterState(
        lo_rank=jnp.zeros((m,), jnp.int32),
        hi_rank=jnp.full((m,), 1, jnp.int32) * n,
        lo_key=jnp.full((m,), -hi_sentinel(dtype) if jnp.issubdtype(dtype, jnp.floating)
                        else jnp.iinfo(dtype).min, dtype),
        hi_key=jnp.full((m,), hi_sentinel(dtype), dtype),
        satisfied=jnp.zeros((m,), bool),
    )

    def round_body(carry, _):
        state, key = carry
        key, sub = jr.split(key)
        gamma = active_union_size(state, targets)
        prob = jnp.minimum(1.0, f_total / jnp.maximum(gamma, 1).astype(jnp.float32))
        vals, n_samp, ovf = _sample_round(local_sorted, state, prob, cap, sub,
                                          kernel_policy=cfg.kernel_policy)
        probes = dispatch.local_sort(
            jax.lax.all_gather(vals, axis_names, tiled=True),
            policy=cfg.kernel_policy)
        local_ranks = dispatch.probe_ranks(local_sorted, probes,
                                           policy=cfg.kernel_policy,
                                           assume_sorted=True)
        ranks = jax.lax.psum(local_ranks, axis_names)
        state = refine(state, probes, ranks, targets, tol)
        return (state, key), (gamma, jax.lax.psum(n_samp, axis_names),
                              jax.lax.psum(ovf, axis_names))

    with spans.scope(spans.SPLITTER_ROUND):   # the rounds and their loop
        (state, _), stats = jax.lax.scan(round_body, (state0, rng), None,
                                         length=k)
    keys, ranks = choose_splitters(state, targets)
    return keys, ranks, stats


def two_stage_sort_sharded(
    local, *, outer_axis, inner_axis, r1, r2, rng,
    hss_cfg: HSSConfig | None = None,
    ex_cfg: ExchangeConfig | None = None,
    stage1_out_slack: float = 2.0,
):
    """shard_map-resident two-stage HSS sort over a (r1, r2) mesh."""
    hss_cfg = hss_cfg or HSSConfig()
    ex_cfg = ex_cfg or ExchangeConfig(kernel_policy=hss_cfg.kernel_policy)
    with spans.scope(spans.LOCAL_SORT):
        local_sorted = dispatch.local_sort(local, policy=hss_cfg.kernel_policy)
    rng1, rng2 = jr.split(rng)

    # ---- stage 1: split into r1 groups, exchange along the outer axis only.
    with spans.scope(spans.SPLITTERS):
        g_keys, _, _ = hss_splitters_general(
            local_sorted, axis_names=(outer_axis, inner_axis),
            num_shards=r1 * r2, num_parts=r1, cfg=hss_cfg, rng=rng1)
    ex1 = dataclasses.replace(ex_cfg, out_slack=stage1_out_slack)
    mid, mid_valid, ovf1 = exchange(
        local_sorted, g_keys, axis_name=outer_axis, p=r1, cfg=ex1,
        eps=hss_cfg.eps)

    # ---- stage 2: full HSS sort within the group along the inner axis.
    # mid is sentinel-padded; group-wide valid count:
    group_n = jax.lax.psum(mid_valid, inner_axis)
    with spans.scope(spans.SPLITTERS):
        s_keys, _, _ = hss_splitters_general(
            mid, axis_names=inner_axis, num_shards=r2, num_parts=r2,
            cfg=hss_cfg, rng=rng2, n_valid=group_n)
    out, n_valid, ovf2 = exchange(
        mid, s_keys, axis_name=inner_axis, p=r2, cfg=ex_cfg, eps=hss_cfg.eps,
        n_valid=mid_valid)
    # Sentinels from stage 1 travel to the last shard's tail; strip by count.
    return out, n_valid, ovf1 + ovf2


def two_stage_sort(x, mesh, outer_axis="outer", inner_axis="inner", seed=0,
                   hss_cfg: HSSConfig | None = None,
                   ex_cfg: ExchangeConfig | None = None):
    """Host-level entry: x (n,) sorted across a 2-D mesh (outer, inner).

    Runs through the shared driver (repro.sort.driver); prefer
    `repro.sort.sort(x, SortSpec(algorithm="multistage"))` in new code.
    """
    from repro.sort import driver as sort_driver
    r1, r2 = mesh.shape[outer_axis], mesh.shape[inner_axis]
    policy = (hss_cfg or HSSConfig()).kernel_policy

    def sort_fn(local, rng):
        out, n_valid, ovf = two_stage_sort_sharded(
            local, outer_axis=outer_axis, inner_axis=inner_axis,
            r1=r1, r2=r2, rng=rng, hss_cfg=hss_cfg, ex_cfg=ex_cfg)
        return (out, n_valid, jnp.zeros((0,), local.dtype),
                jnp.zeros((0,), jnp.int32), ovf, jnp.zeros((0,), jnp.int32))

    out, counts, _, _, ovf, _ = sort_driver.run(
        sort_fn, x, mesh=mesh, axis_names=(outer_axis, inner_axis), seed=seed,
        local_sort_fn=dispatch.local_sort_fn(policy))
    return out.reshape(r1, r2, -1), counts.reshape(r1, r2), ovf
