"""The two jax constructors every sharded call site shares.

`make_mesh` builds meshes with Auto axes. `jax.make_mesh` defaults to
Explicit axes, under which the sort's shard bodies (scatters and reshapes
of sharded operands), `with_sharding_constraint` and shard_map closures
over sharded inputs are rejected; every mesh in the repo is built here.

`shard_map` is `jax.shard_map` with the replication check off: shard
bodies return per-shard results that the out_specs declare replicated
(splitters, stats) after a collective made them so.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """`jax.make_mesh` with every axis Auto."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map(..., check_vma=False)`."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
