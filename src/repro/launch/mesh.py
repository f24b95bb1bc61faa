"""Production mesh + per-arch ParallelCtx construction.

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips — the pod axis joins the FSDP/data group (DCN-friendly:
only gradient reduce-scatter/all-gather cross pods; all TP collectives stay
on intra-pod ICI).
"""
from __future__ import annotations

import jax

from repro.models.config import ArchConfig
from repro.parallel.ctx import ParallelCtx
from repro.parallel.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False, devices=None):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if devices is None:
        n = 512 if multi_pod else 256
        devices = jax.devices()[:n]
    import numpy as np
    dev = np.asarray(devices).reshape(shape)
    return make_mesh(shape, axes, devices=dev.reshape(-1))


def make_ctx(cfg: ArchConfig, mesh, *, multi_pod: bool = False) -> ParallelCtx:
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    tp = mesh.shape["model"]
    extra = []
    if cfg.n_kv_heads and cfg.n_kv_heads % tp != 0:
        extra.append(("tp_kv", None))   # replicate small KV-head counts
    return ParallelCtx(
        mesh=mesh,
        dp_axes=dp_axes,
        tp_axis="model",
        shard_heads=cfg.heads_shardable(tp),
        rules_extra=tuple(extra),
    )
