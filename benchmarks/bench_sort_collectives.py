"""HLO-level communication volume of splitter determination on the production
mesh — the paper's own metric (Table 2) measured from compiled programs.

Lowers HSS / sample sort (random) / AMS splitter determination for p = 256
shards against the 16x16 mesh and sums per-device collective bytes. This is
the framework-native validation of the paper's communication-complexity
table. It is a CPU-only analysis that needs 512 simulated host devices, so
it runs in a process of its own, never inside `benchmarks.run`:

    PYTHONPATH=src python -m benchmarks.bench_sort_collectives
"""
from __future__ import annotations

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import jax.random as jr
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit
from repro.launch.dryrun import collective_bytes
from repro.parallel.compat import make_mesh, shard_map
from repro.sort import ShardCtx, SortSpec, get_partitioner

P_SHARDS = 256
N_LOCAL = 1 << 20   # 1M keys/shard => N = 268M


def lower_bytes(per_shard):
    mesh = make_mesh((P_SHARDS,), ("sort",),
                     devices=jax.devices()[:P_SHARDS])
    f = jax.jit(shard_map(per_shard, mesh=mesh,
                          in_specs=(P("sort"), P()), out_specs=P()))
    xs = jax.ShapeDtypeStruct((P_SHARDS, N_LOCAL), jnp.int32)
    txt = f.lower(xs, jr.key(0)).compile().as_text()
    return collective_bytes(txt)


def splitter_shard(algorithm, **spec_kw):
    # splitter determination only, through the partitioner registry —
    # the exact strategy objects the sort() front-door runs
    part = get_partitioner(algorithm)
    spec = SortSpec(algorithm=algorithm, eps=0.05, **spec_kw)
    def per_shard(block, key):
        local = jnp.sort(block.reshape(-1))
        rng = jr.fold_in(key, jax.lax.axis_index("sort"))
        ctx = ShardCtx(spec=spec, axis_names=("sort",), sizes=(P_SHARDS,),
                       rng=rng)
        keys, _, _, _ = part.splitters(local, ctx)
        return keys
    return per_shard


def two_stage_shard():
    # 16x16 two-stage splitter determination (paper Table 3 / Sec 6.1):
    # stage-1 16 groups + stage-2 within-group, measured on the 2-D mesh
    from repro.core.common import HSSConfig
    from repro.core.multistage import hss_splitters_general
    mesh2 = make_mesh((16, 16), ("outer", "inner"),
                      devices=jax.devices()[:256])
    def body(block, key):
        local = jnp.sort(block.reshape(-1))
        me = jax.lax.axis_index("outer") * 16 + jax.lax.axis_index("inner")
        rng = jr.fold_in(key, me)
        g, _, _ = hss_splitters_general(
            local, axis_names=("outer", "inner"), num_shards=256,
            num_parts=16, cfg=HSSConfig(eps=0.05), rng=rng)
        s, _, _ = hss_splitters_general(
            local, axis_names="inner", num_shards=16, num_parts=16,
            cfg=HSSConfig(eps=0.05), rng=jr.fold_in(rng, 1))
        return g, s
    f = jax.jit(shard_map(body, mesh=mesh2,
                          in_specs=(P("outer", "inner"), P()),
                          out_specs=(P(), P())))
    xs = jax.ShapeDtypeStruct((16, 16, N_LOCAL), jnp.int32)
    txt = f.lower(xs, jr.key(0)).compile().as_text()
    return collective_bytes(txt)


def run():
    if jax.device_count() < P_SHARDS or jax.default_backend() != "cpu":
        raise RuntimeError(
            f"needs {P_SHARDS} simulated CPU devices: run it alone with "
            "`python -m benchmarks.bench_sort_collectives`")
    h = lower_bytes(splitter_shard("hss"))["total_bytes"]
    # Theorem 3.1 sample size for eps=0.05: 2 p log2(N) / eps^2
    s = lower_bytes(splitter_shard(
        "sample_random",
        total_sample=int(2 * P_SHARDS * 28 / 0.05 ** 2)))["total_bytes"]
    # Lemma A.1 sample (registry default)
    a = lower_bytes(splitter_shard("ams"))["total_bytes"]
    t2 = two_stage_shard()["total_bytes"]
    return [
        ("sortcoll/hss_splitters_bytes", None,
         f"{h} B/dev (p=256, 1M keys/shard, eps=5%)"),
        ("sortcoll/ams_splitters_bytes", None,
         f"{a} B/dev ratio_vs_hss={a / max(h, 1):.1f}x (Lemma A.1 sample)"),
        ("sortcoll/samplesort_splitters_bytes", None,
         f"{s} B/dev ratio_vs_hss={s / max(h, 1):.1f}x "
         "(Table 2's communication gap, from compiled HLO)"),
        ("sortcoll/hss_2stage_bytes", None,
         f"{t2} B/dev (16x16 two-stage, both stages; Table 3)"),
    ]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    emit(run())
