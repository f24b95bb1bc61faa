"""Build traceable shard programs for the static-analysis matrix.

The lint CLI and the analyzer tests need the *programs we ship* — a
partitioner's shard pipeline under shard_map, the semisort splitter path,
the top-k pruning program — as plain callables that `jax.make_jaxpr` can
trace with ShapeDtypeStruct arguments (no data, no execution). This module
builds them exactly the way `repro.sort.driver` does: same compat
shard_map wrapper, same in/out specs, same mesh factoring (multistage gets
its 2-D mesh from `Partitioner.mesh_axes`).

Tracing happens on whatever platform runs the lint: it needs no backend
support, so even the `ragged` exchange, which only XLA:TPU compiles, is
traced on the CPU.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import jax.random as jr
from jax.sharding import PartitionSpec as P

from repro.parallel.compat import make_mesh, shard_map
from repro.sort.partitioners import ShardCtx, get_partitioner
from repro.sort.spec import SortSpec

__all__ = [
    "partitioner_program",
    "splitters_program",
    "make_topk_program",
]


def _mesh_for(part, spec: SortSpec, p: int):
    axes = part.mesh_axes(spec, p)
    names = tuple(a for a, _ in axes)
    sizes = tuple(s for _, s in axes)
    assert math.prod(sizes) == p, (axes, p)
    return make_mesh(sizes, names), names, sizes


def partitioner_program(algo: str, *, exchange: str = "dense",
                        batch: Optional[int] = None, p: int = 8,
                        n_local: int = 128, dtype=jnp.int32,
                        spec: Optional[SortSpec] = None):
    """The full shard pipeline (local sort -> splitters -> exchange) of one
    partitioner, wrapped in shard_map the way the driver wraps it.

    Returns ``(fn, args)`` ready for ``jax.make_jaxpr(fn)(*args)``;
    ``batch=None`` builds the single-request program, an int builds the
    batched one.
    """
    part = get_partitioner(algo)
    spec = spec or SortSpec(algorithm=algo, exchange=exchange)
    mesh, names, sizes = _mesh_for(part, spec, p)
    ctx = ShardCtx(spec=spec, axis_names=names, sizes=sizes, rng=None)
    naxes = len(names)

    lead = (1,) * naxes   # the driver's leading shard dims (one per axis)

    if batch is None:
        def per_shard(block, key):
            rng = jr.fold_in(key, jax.lax.axis_index(names[0]))
            out = part.sharded(block.reshape(-1), rng, ctx)[0]
            return out.reshape(lead + out.shape)

        sharded = P(*names)
        fn = shard_map(per_shard, mesh=mesh, in_specs=(sharded, P()),
                       out_specs=sharded)
        shape = sizes + (n_local,)
    else:
        def per_shard(block, key):
            rng = jr.fold_in(key, jax.lax.axis_index(names[0]))
            out = part.sharded_batched(block.reshape(batch, n_local),
                                       rng, ctx)[0]
            return out.reshape((batch,) + lead + out.shape[1:])

        sharded = P(None, *names)
        fn = shard_map(per_shard, mesh=mesh, in_specs=(sharded, P()),
                       out_specs=sharded)
        shape = (batch,) + sizes + (n_local,)
    return fn, (jax.ShapeDtypeStruct(shape, dtype), jr.key(0))


def splitters_program(algo: str, *, batch: Optional[int] = None, p: int = 8,
                      n_local: int = 128, dtype=jnp.int32,
                      spec: Optional[SortSpec] = None):
    """Splitter determination only (no exchange): the phase the per-round
    contracts constrain. Input rows arrive pre-sorted in the real pipeline;
    the program sorts them inline like `Partitioner.sharded` does."""
    part = get_partitioner(algo)
    spec = spec or SortSpec(algorithm=algo)
    mesh, names, sizes = _mesh_for(part, spec, p)
    if batch is None:
        def per_shard(block, key):
            rng = jr.fold_in(key, jax.lax.axis_index(names[0]))
            ls = jnp.sort(block.reshape(-1))
            keys, _, _, _ = part.splitters(
                ls, ShardCtx(spec=spec, axis_names=names, sizes=sizes,
                             rng=rng))
            return keys

        fn = shard_map(per_shard, mesh=mesh, in_specs=(P(*names), P()),
                       out_specs=P())
        shape = sizes + (n_local,)
    else:
        def per_shard(block, key):
            rng = jr.fold_in(key, jax.lax.axis_index(names[0]))
            ls = jnp.sort(block.reshape(batch, n_local), axis=-1)
            keys, _, _, _ = part.splitters_batched(
                ls, ShardCtx(spec=spec, axis_names=names, sizes=sizes,
                             rng=rng))
            return keys

        fn = shard_map(per_shard, mesh=mesh, in_specs=(P(None, *names), P()),
                       out_specs=P())
        shape = (batch,) + sizes + (n_local,)
    return fn, (jax.ShapeDtypeStruct(shape, dtype), jr.key(0))


def make_topk_program(*, k: int = 10, batch: Optional[int] = None,
                      p: int = 8, n_local: int = 128, dtype=jnp.int32):
    """The top-k pruning program (semisort front door), plus its pruned
    width c — the operand the contract pins the single all_gather to."""
    from repro.core.common import round_up
    from repro.sort import driver
    from repro.sort.semisort import topk_program

    c = min(round_up(k, 8), n_local)
    mesh_plan = driver.resolve_mesh(None, ("sort",))
    prog = topk_program(mesh_plan, n_local, c, k, batch=batch)
    shape = (p, n_local) if batch is None else (batch, p, n_local)
    return prog, (jax.ShapeDtypeStruct(shape, dtype),), c
