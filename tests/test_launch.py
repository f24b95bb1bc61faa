"""Launch-layer units: HLO collective parser, specs, flops accounting."""
import jax


def test_collective_parser_counts_bytes():
    """Optimized-HLO form: operands are bare names (no types) — bytes must
    come from the output shape."""
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %all-gather.7 = bf16[16,1024]{1,0} all-gather(%p0), dims={0}
  %all-reduce.3 = f32[256]{0} all-reduce(%x), channel_id=4, replica_groups=[16,16]<=[256], to_apply=%add
  %all-to-all.9 = bf16[8,64]{1,0} all-to-all(%y), dimensions={0}
  %ag-start = (bf16[1,8]{1,0}, bf16[4,8]{1,0}) all-gather-start(%z), dims={0}
  %ag-done = bf16[4,8]{1,0} all-gather-done(%ag-start)
  %reduce-scatter.2 = f32[64]{0} reduce-scatter(%r), channel_id=9, replica_groups=[32,8]<=[256], to_apply=%add
  %collective-permute.1 = f32[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    out = collective_bytes(hlo)
    assert out["counts"]["all-gather"] == 2          # start counted, done not
    assert out["bytes"]["all-gather"] == 16 * 1024 * 2 + 4 * 8 * 2
    assert out["bytes"]["all-reduce"] == 256 * 4 * 2  # 2x ring multiplier
    assert out["bytes"]["all-to-all"] == 8 * 64 * 2
    assert out["bytes"]["reduce-scatter"] == 64 * 4 * 8  # x group size
    assert out["bytes"]["collective-permute"] == 128 * 4
    assert out["total_bytes"] == sum(out["bytes"].values())


def test_param_count_orders_of_magnitude():
    from repro.configs import get_config
    from repro.models.flops import active_params, total_params
    # published param counts (order-of-magnitude sanity, padding included)
    expect = {"granite-34b": 34e9, "granite-20b": 20e9,
              "starcoder2-3b": 3e9, "stablelm-12b": 12e9,
              "kimi-k2-1t-a32b": 1e12, "pixtral-12b": 12e9}
    for arch, n in expect.items():
        got = total_params(get_config(arch))
        assert 0.55 * n < got < 1.8 * n, (arch, got)
    # MoE active << total
    k = get_config("kimi-k2-1t-a32b")
    assert active_params(k) < 0.05 * total_params(k)
    phi = get_config("phi3.5-moe-42b-a6.6b")
    assert 36e9 < total_params(phi) < 48e9
    assert 5e9 < active_params(phi) < 9e9


def test_cells_cover_40():
    from repro.configs import ARCH_IDS, cells
    cs = cells(ARCH_IDS)
    assert len(cs) == 40
    skips = [c for c in cs if c[2].startswith("SKIP")]
    assert len(skips) == 8      # all long_500k except zamba2 + mamba2
    assert all(c[1] == "long_500k" for c in skips)


def test_input_specs_shardable():
    """batch_specs/decode_specs stay consistent with a small mesh."""
    from repro.configs import SHAPES, smoke_config
    from repro.launch.specs import batch_specs, decode_specs
    from repro.parallel.compat import make_mesh
    from repro.parallel.ctx import ParallelCtx
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = smoke_config("granite-34b")
    ctx = ParallelCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    bs, bsh = batch_specs(cfg, SHAPES["train_4k"], ctx)
    assert bs["tokens"].shape == (256, 4096)
    assert set(bs) == set(bsh)
    (cache, tok, pos), (csh, tsh, psh) = decode_specs(cfg, SHAPES["decode_32k"], ctx)
    assert tok.shape == (128, 1)
    assert jax.tree.structure(cache) == jax.tree.structure(csh)


def test_heads_shardable_policy():
    from repro.configs import get_config
    assert not get_config("whisper-large-v3").heads_shardable(16)   # 20H
    assert not get_config("starcoder2-3b").heads_shardable(16)      # 24H
    assert get_config("granite-34b").heads_shardable(16)            # 48H
    assert get_config("kimi-k2-1t-a32b").heads_shardable(16)        # 64H
