"""Paper Figure 6: splitter-determination (histogramming) cost vs p.

Real wall time on host devices for small p; simulator sample-volume (the
quantity the paper's O(p log log p) bound governs) for paper-scale p."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import timeit
from repro.core import HSSConfig
from repro.core.splitters import hss_splitters
from repro.core import simulator as sim
from repro.parallel.compat import make_mesh, shard_map


def _splitter_time(p: int, n_per: int, eps: float) -> float:
    mesh = make_mesh((p,), ("sort",), devices=jax.devices()[:p])
    rng = np.random.default_rng(0)
    xs = jnp.sort(jnp.asarray(
        rng.permutation(p * n_per).astype(np.int32)).reshape(p, n_per), axis=1)

    def per_shard(block, key):
        import jax.random as jr
        local = block.reshape(-1)
        r = jr.fold_in(key, jax.lax.axis_index("sort"))
        keys, ranks, stats = hss_splitters(
            local, axis_name="sort", p=p, cfg=HSSConfig(eps=eps), rng=r)
        return keys

    f = jax.jit(shard_map(per_shard, mesh=mesh,
                          in_specs=(P("sort"), P()), out_specs=P()))
    import jax.random as jr
    key = jr.key(0)
    return timeit(lambda: f(xs, key))


def run(n_per: int = 65536, eps: float = 0.02):
    rows = []
    for p in (2, 4, 8):
        if p > len(jax.devices()):
            continue
        us = _splitter_time(p, n_per, eps)
        rows.append((f"fig6/splitter_time_p{p}", round(us, 1), "real shards"))
    # paper-scale growth of the histogram volume (simulator)
    for p in (4096, 16384, 65536):
        r = sim.simulate_hss(p, 2048, eps=eps, sample_per_round=5 * p, seed=2)
        rows.append((f"fig6/sample_volume_p{p}", None,
                     f"total_sample={r.total_sample} per_p="
                     f"{r.total_sample / p:.2f} rounds={r.rounds_used}"))
    return rows
