"""First-class integration bench: MoE token dispatch (the paper's partitioning
problem inside the LM stack) — balanced-capacity dispatch drop rates + wall
time of the shard_map a2a dispatch on host devices."""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timeit
from repro.configs import smoke_config
from repro.models.moe import moe_ffn
from repro.parallel.ctx import ParallelCtx
from repro.parallel.compat import make_mesh


def run():
    rows = []
    p = min(8, len(jax.devices()))
    mesh = make_mesh((1, p), ("data", "model"))
    cfg = smoke_config("phi3.5-moe-42b-a6.6b")
    cfg = dataclasses.replace(cfg, n_experts=8, top_k=2, d_model=128,
                              d_ff_expert=256)
    ctx = ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    rng = np.random.default_rng(0)
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    params = {
        "router": jnp.asarray(rng.standard_normal((d, E)), jnp.float32) * 0.2,
        "w1": jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32) * 0.05,
        "w3": jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32) * 0.05,
        "w2": jnp.asarray(rng.standard_normal((E, f, d)), jnp.float32) * 0.05,
    }
    x = jnp.asarray(rng.standard_normal((4, 64 * p, d)), jnp.float32)

    for cf in (1.0, 1.25, 2.0):
        c = dataclasses.replace(cfg, moe_capacity_factor=cf)
        fn = jax.jit(lambda x, p_: moe_ffn(x, p_, c, ctx))
        y, aux = fn(x, params)
        us = timeit(lambda: fn(x, params)[0])
        total = x.shape[0] * x.shape[1] * cfg.top_k
        rows.append((f"moe/dispatch_cf{cf}", round(us, 1),
                     f"dropped={int(aux['dropped'])}/{total} "
                     f"(capacity-bounded a2a, ep={p})"))
    return rows
