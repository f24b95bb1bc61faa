"""The Pallas kernels compile for a TPU v5e chip at shard-scale sizes.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unsupported primitives, blocks that break the (8, 128) tiling, layouts
XLA and Mosaic disagree on. These tests compile each kernel family for a
described — not attached — v5e chip, so a refusal fails here instead of on
the chip. Nothing runs; results are checked by the parity tests and by
`chip_smoke.py` on the chip. The ragged exchange, which only XLA:TPU
compiles, is compiled here too, over the four chips of the described host.
"""
import re

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


def _kernel_op_names(hlo: str) -> set:
    """Names of the compiled program's Pallas launches, numbers dropped."""
    return {re.sub(r"\.\d+$", "", m) for m in re.findall(
        r"%(\S+) = \S+ custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo)}


@pytest.mark.parametrize("entry, shape, want", [
    # past one VMEM block (2^16 keys) each takes strided HBM passes too
    ("merge", (2, 1 << 16), {"merge_sorted_runs_network",
                             "merge_sorted_runs_strided"}),
    ("sort", (1 << 17,), {"local_sort_network", "local_sort_strided"}),
    ("ranks", (1 << 16,), {"probe_ranks_histogram"}),
])
def test_kernels_carry_their_names_on_v5e(one_chip, entry, shape, want):
    """Each Pallas launch is named by `repro.runtime.spans`: the merge's
    launches all start with `merge_sorted_runs`, which the benchmark's
    merge reader matches, and the local sort's never do."""
    x = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    if entry == "merge":
        hlo = _compile_text(
            lambda r: mops.merge_sorted_runs(r, interpret=False), x)
    elif entry == "sort":
        hlo = _compile_text(lambda v: bops.local_sort(v, interpret=False), x)
    else:
        probes = jax.ShapeDtypeStruct((255,), jnp.int32, sharding=one_chip)
        hlo = _compile_text(
            lambda k, q: hops.probe_ranks(k, q, interpret=False), x, probes)
    assert _kernel_op_names(hlo) == want


def test_dense_exchange_sends_without_an_element_gather_on_v5e(topo):
    """The dense exchange cuts each destination's send window out of the
    sorted shard as one contiguous copy. An element-indexed gather of the
    same windows costs XLA:TPU a tile per key (8.6 s a call on a TPU v5e
    at 2^27 keys per chip); the only gathers left under the `exchange`
    scope are the destination slices' binary searches for the p - 1
    splitters."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.common import HSSConfig
    from repro.core.exchange import ExchangeConfig
    from repro.core.hss import hss_sort_sharded
    from repro.parallel.compat import make_mesh, shard_map

    p, n = 4, 1 << 22
    mesh = make_mesh((p,), ("sort",), devices=topo.devices[:p])
    hss_cfg = HSSConfig(eps=0.05, kernel_policy="xla")
    ex_cfg = ExchangeConfig(strategy="dense", kernel_policy="xla")

    def body(local, key):
        out, n_valid, *_ = hss_sort_sharded(
            local, axis_name="sort", p=p, rng=key[0], hss_cfg=hss_cfg,
            ex_cfg=ex_cfg)
        return out[None], n_valid[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("sort"), P()),
                          out_specs=(P("sort"), P("sort"))))
    keys = jax.ShapeDtypeStruct((p * n,), jnp.int32,
                                sharding=NamedSharding(mesh, P("sort")))
    rng = jax.ShapeDtypeStruct((1, 2), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    hlo = f.lower(keys, rng).compile().as_text()
    gathers = re.findall(r"= (\S+) gather\([^\n]*op_name=\"([^\"]*)\"", hlo)
    in_exchange = [(shape, path) for shape, path in gathers
                   if "/exchange/" in path]
    assert in_exchange, "no gather carries the exchange scope"
    for shape, path in in_exchange:
        assert "searchsorted" in path and shape.startswith(f"s32[{p - 1}]"), \
            (shape, path)


def test_record_launch_compiles_for_v5e(topo):
    """The record sort's launch (local sort, splitter rounds over key
    tuples, the all-to-all of whole records, the merge) compiles for the
    four chips of a described v5e host: every sort of a shard's records
    has two operands and one key (XLA adds a third, the positions), and
    no gather reads more than a sample's rows."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.parallel.compat import make_mesh, shard_map
    from repro.sort import SortSpec
    from repro.sort.partitioners import ShardCtx, get_partitioner

    p, n = 4, 1 << 16
    mesh = make_mesh((p,), ("sort",), devices=topo.devices[:p])
    ctx = ShardCtx(spec=SortSpec(mesh=mesh), axis_names=("sort",),
                   sizes=(p,), rng=None)
    part = get_partitioner("hss")

    def body(local, key):
        out, n_valid, *_ = part.sharded_records(local, key[0], ctx)
        return out[None], n_valid[None]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(None, "sort"), P()),
                          out_specs=(P("sort"), P("sort"))))
    records = jax.ShapeDtypeStruct((25, p * n), jnp.uint32,
                                   sharding=NamedSharding(mesh,
                                                          P(None, "sort")))
    rng = jax.ShapeDtypeStruct((1, 2), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    hlo = f.lower(records, rng).compile().as_text()
    assert "all-to-all" in hlo
    sorts = re.findall(r"= \((.*?)\) sort\(", hlo)
    big = [s for s in sorts if f"{n}]" in s or f",{n}]" in s]
    assert big and all(s.count("[") <= 3 for s in big), big
    for shape in re.findall(r"= (\S+) gather\(", hlo):
        assert f"{n}" not in shape, shape
