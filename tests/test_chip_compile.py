"""The Pallas kernels compile for a TPU v5e chip at shard-scale sizes.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unsupported primitives, blocks that break the (8, 128) tiling, layouts
XLA and Mosaic disagree on. These tests compile each kernel family for a
described — not attached — v5e chip, so a refusal fails here instead of on
the chip. Nothing runs; results are checked by the parity tests and by
`chip_smoke.py` on the chip. The ragged exchange, which only XLA:TPU
compiles, is compiled here too, over the four chips of the described host.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitonic_sort import ops as bops
from repro.kernels.histogram import ops as hops
from repro.kernels.merge import ops as mops

pytestmark = pytest.mark.kernels


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_bitonic_local_sort_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((1 << 22,), jnp.int32, sharding=one_chip)
    hlo = _compile_text(lambda v: bops.local_sort(v, interpret=False), x)
    assert "tpu_custom_call" in hlo


def test_probe_ranks_compiles_for_v5e(one_chip):
    keys = jax.ShapeDtypeStruct((1 << 24,), jnp.int32, sharding=one_chip)
    probes = jax.ShapeDtypeStruct((255,), jnp.int32, sharding=one_chip)
    hlo = _compile_text(
        lambda k, q: hops.probe_ranks(k, q, interpret=False), keys, probes)
    assert "tpu_custom_call" in hlo


def test_merge_sorted_runs_compiles_for_v5e(one_chip):
    runs = jax.ShapeDtypeStruct((4, 1 << 22), jnp.int32, sharding=one_chip)
    hlo = _compile_text(
        lambda r: mops.merge_sorted_runs(r, interpret=False), runs)
    assert "tpu_custom_call" in hlo


def test_ragged_exchange_compiles_for_v5e_in_linear_memory(topo):
    """XLA:TPU lays a 1-D ragged_all_to_all operand out one key per
    128-lane row; the exchange sends rows of keys instead."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.exchange import ExchangeConfig, exchange_ragged
    from repro.parallel.compat import make_mesh, shard_map

    p, n = 4, 1 << 20
    mesh = make_mesh((p,), ("sort",), devices=topo.devices[:p])
    cfg = ExchangeConfig(strategy="ragged", kernel_policy="xla")

    def body(local, splitters):
        out, n_valid, _ = exchange_ragged(local, splitters, axis_name="sort",
                                          p=p, cfg=cfg, eps=0.05)
        return out[None], n_valid[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("sort"), P()),
                          out_specs=(P("sort"), P("sort"))))
    keys = jax.ShapeDtypeStruct((p * n,), jnp.int32,
                                sharding=NamedSharding(mesh, P("sort")))
    splitters = jax.ShapeDtypeStruct((p - 1,), jnp.int32,
                                     sharding=NamedSharding(mesh, P()))
    compiled = f.lower(keys, splitters).compile()
    assert "ragged-all-to-all" in compiled.as_text()
    # one key per 128-lane row would need 512 bytes of each buffer per key
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 4 * n
