"""Paper Figure 7 (ChaNGa) analog: iterative application re-sorting
slowly-drifting keys every step.

The paper's cosmology keys move a little per timestep; our analog is MoE
router drift / data-pipeline length drift. The measured effect is the same
one the paper exploits: warm-starting the splitter intervals from the
previous step's splitters collapses gamma_0 and cuts histogramming rounds."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timeit
from repro.core import ExchangeConfig, HSSConfig, hss_sort
from repro.parallel.compat import make_mesh


def run(n_per: int = 32768, eps: float = 0.05, steps: int = 4):
    p = min(8, len(jax.devices()))
    mesh = make_mesh((p,), ("sort",), devices=jax.devices()[:p])
    n = p * n_per
    rng = np.random.default_rng(5)
    x = rng.permutation(n * 8)[:n].astype(np.int32)

    rows = []
    cfg = HSSConfig(eps=eps)
    ex = ExchangeConfig(strategy="allgather")
    probes = None
    cold_rounds, warm_rounds = [], []
    for step in range(steps):
        res_cold = hss_sort(jnp.asarray(x), mesh=mesh, hss_cfg=cfg, ex_cfg=ex,
                            seed=step)
        cold_rounds.append(int(res_cold.stats.rounds_used))
        if probes is not None:
            res_warm = hss_sort(jnp.asarray(x), mesh=mesh, hss_cfg=cfg,
                                ex_cfg=ex, seed=step,
                                initial_probes=jnp.sort(probes))
            warm_rounds.append(int(res_warm.stats.rounds_used))
            g0 = int(res_warm.stats.gamma_size[0])
            rows.append((f"fig7/step{step}", None,
                         f"warm_rounds={warm_rounds[-1]} "
                         f"cold_rounds={cold_rounds[-1]} gamma0_frac={g0 / n:.4f}"))
        probes = res_cold.splitter_keys
        # drift: keys move by a small random walk (the ChaNGa regime)
        x = (x + rng.integers(-50, 51, size=n)).astype(np.int32)

    # An iterative app warm-starting from last step's splitters also
    # *configures* fewer rounds (the fixed-k scan otherwise still executes k
    # no-op rounds) — that is the ChaNGa integration pattern.
    warm_cfg = HSSConfig(eps=eps, rounds=1)
    us_cold = timeit(lambda: hss_sort(jnp.asarray(x), mesh=mesh, hss_cfg=cfg,
                                      ex_cfg=ex).shards)
    us_warm = timeit(lambda: hss_sort(
        jnp.asarray(x), mesh=mesh, hss_cfg=warm_cfg, ex_cfg=ex,
        initial_probes=jnp.sort(probes)).shards)
    res = hss_sort(jnp.asarray(x), mesh=mesh, hss_cfg=warm_cfg, ex_cfg=ex,
                   initial_probes=jnp.sort(probes))
    ok = int(res.overflow) == 0 and bool(
        (np.asarray(res.counts) <= (1 + eps) * n / p + 1).all())
    rows.append(("fig7/cold", round(us_cold, 1), "4 histogram rounds"))
    rows.append(("fig7/warm", round(us_warm, 1),
                 f"speedup={us_cold / us_warm:.2f}x balanced={ok} "
                 "(warm-start + 1 round; paper: up to 25%)"))
    return rows
