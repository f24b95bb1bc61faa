"""ParallelCtx: the mesh + logical-axis rules threaded through model code.

Logical axes used by the model stack:
  fsdp      parameter d_model-ish dims, ZeRO-3 sharded over the data axes
  tp        tensor-parallel dims (d_ff, experts, vocab, sharded heads)
  tp_heads  attention head dims — 'model' when head counts divide the TP size,
            else None (whisper 20H, starcoder2 24H: attention falls back to
            context sharding; DESIGN.md Section 5)
  dp        batch dims of activations
  sp        context/sequence dim of activations (sequence parallelism)

A ctx with a 1x1 mesh (local_ctx) makes every rule a no-op so the same model
code runs unsharded in unit tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.parallel.compat import make_mesh


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    mesh: Any                      # jax.sharding.Mesh
    dp_axes: tuple                 # e.g. ("pod", "data") or ("data",)
    tp_axis: str | None            # "model"
    shard_heads: bool = True       # False => replicate heads, shard context
    seq_parallel: bool = True      # shard residual-stream context over TP
    tp_seq_collectives: bool = False  # Megatron-SP: constrain TP projection
    # outputs context-sharded so XLA emits reduce-scatter (1x bytes) instead
    # of all-reduce (2x) into the sequence-parallel residual stream
    rules_extra: tuple = ()

    @property
    def dp_size(self) -> int:
        size = 1
        for a in self.dp_axes:
            size *= self.mesh.shape[a]
        return size

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis] if self.tp_axis else 1

    def rules(self) -> dict:
        r = {
            "fsdp": tuple(self.dp_axes) if self.dp_axes else None,
            "tp": self.tp_axis,
            "tp_exp": self.tp_axis,
            "tp_heads": self.tp_axis if self.shard_heads else None,
            "dp": tuple(self.dp_axes) if self.dp_axes else None,
            "sp": (self.tp_axis if not self.shard_heads else None),
            "sp_seq": (self.tp_axis if self.seq_parallel else None),
            "sp_always": self.tp_axis,
            None: None,
        }
        r.update(dict(self.rules_extra))
        return r

    def spec(self, *names) -> P:
        rules = self.rules()
        return P(*[rules.get(n, None) for n in names])

    def named(self, *names):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, self.spec(*names))


def local_ctx() -> ParallelCtx:
    """1-device ctx for unit tests: named axes exist but have size 1, so every
    collective and constraint is a well-formed no-op."""
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    return ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
