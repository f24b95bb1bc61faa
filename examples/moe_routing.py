"""HSS inside the LM stack: capacity-bounded MoE expert dispatch.

Token->expert dispatch is the paper's partitioning problem (DESIGN.md Sec. 4):
N tokens must be split across expert shards under a static (1+eps) capacity.
This example routes a batch through the shard_map a2a dispatch at several
capacity factors and shows the drop/balance trade-off, then demonstrates the
pure-sort view: balanced re-partitioning of (expert_id, token) keys through
the `repro.sort` front-door (implicit tagging is automatic for the
duplicate-heavy expert ids; the returned indices ARE the token routing).

    PYTHONPATH=src python examples/moe_routing.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models.moe import moe_ffn
from repro.parallel.ctx import ParallelCtx
from repro.sort import SortSpec, sort
from repro.parallel.compat import make_mesh

p = min(8, len(jax.devices()))
mesh = make_mesh((1, p), ("data", "model"))
ctx = ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")

cfg = dataclasses.replace(smoke_config("phi3.5-moe-42b-a6.6b"),
                          n_experts=8, top_k=2, d_model=128, d_ff_expert=256)
rng = np.random.default_rng(0)
d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
params = {
    "router": jnp.asarray(rng.standard_normal((d, E)), jnp.float32) * 0.3,
    "w1": jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32) * 0.05,
    "w3": jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32) * 0.05,
    "w2": jnp.asarray(rng.standard_normal((E, f, d)), jnp.float32) * 0.05,
}
x = jnp.asarray(rng.standard_normal((2, 128 * p, d)), jnp.float32)
tokens = x.shape[0] * x.shape[1] * cfg.top_k

print("== shard_map a2a dispatch (capacity-bounded, the MoE fast path) ==")
for cf in (1.0, 1.5, 3.0):
    c = dataclasses.replace(cfg, moe_capacity_factor=cf)
    y, aux = jax.jit(lambda x, pr: moe_ffn(x, pr, c, ctx))(x, params)
    print(f"  capacity_factor={cf:<4} dropped {int(aux['dropped']):4d} "
          f"of {tokens} assignments")

print("== pure-sort view: HSS over (expert_id, token) keys ==")
# expert assignment keys duplicate heavily (E distinct values); the adapter
# layer tags them automatically and returns the token indices per shard
logits = np.asarray(x).reshape(-1, d) @ np.asarray(params["router"])
eids = np.argsort(-logits, axis=-1)[:, :cfg.top_k].reshape(-1).astype(np.int32)
n = eids.size
res = sort(jnp.asarray(eids),
           SortSpec(eps=0.05, exchange="allgather", stable=True))
print(f"  tokens per shard after HSS partition: {np.asarray(res.counts)}")
print(f"  (1+eps) cap: {(1 + 0.05) * n / p:.0f}; overflow={int(res.overflow)}"
      f"; rounds={int(res.stats.rounds_used)}")
print(f"  routed token ids, shard 0 head: {np.asarray(res.indices[0, :6])}")
