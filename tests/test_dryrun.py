"""Integration tests for the dry-run launch path (subprocess with 8 fake
devices — the production 512-device pass runs via repro.launch.dryrun)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import dataclasses as dc
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import SHAPES, reduce_for_smoke, ShapeSpec
    from repro.launch import dryrun as dr
    from jax.sharding import AxisType
    from repro.roofline import analysis as roofline

    auto2 = (AxisType.Auto, AxisType.Auto)
    mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=auto2)

    # small shapes so the compile stays quick
    shape_train = ShapeSpec("t", 128, 8, "train")
    shape_decode = ShapeSpec("d", 256, 8, "decode")

    for arch in ("smollm-360m", "mixtral-8x22b", "falcon-mamba-7b"):
        cfg = reduce_for_smoke(get_config(arch))
        cfg = dc.replace(cfg, param_dtype="bfloat16", remat="full")
        for shape in (shape_train, shape_decode):
            compiled = dr._compile(cfg, shape, mesh, 1)
            cost = compiled.cost_analysis()
            assert cost.get("flops", 0) > 0, (arch, shape.mode)
            mem = roofline.memory_stats(compiled)
            assert mem["total_bytes"] > 0
            print(f"{arch} {shape.mode} OK flops={cost['flops']:.2e}")

    # sanitize_spec: non-divisible dims degrade to unsharded
    s = dr.sanitize_spec(P("model", "data"), (51867, 64), mesh)  # odd dim
    assert tuple(s) == (None, "data"), s
    s = dr.sanitize_spec(P(("pod", "data"), None), (128, 4), mesh)
    assert tuple(s) == ("data", None), s  # 'pod' absent on this mesh

    # collective parsing: FSDP all-gathers must appear
    cfg = dc.replace(reduce_for_smoke(get_config("smollm-360m")),
                     param_dtype="bfloat16", scan_layers=False)
    compiled = dr._compile(cfg, shape_train, mesh, 1)
    stats = roofline.parse_collectives(compiled.as_text())
    assert stats.modeled_bytes > 0 and stats.counts, stats.counts
    print("collectives OK", stats.counts)

    # shard_map MoE: both variants must match the meshless oracle
    import jax.numpy as jnp
    from repro.models.moe import init_moe, moe, moe_sharded
    cfg = dc.replace(reduce_for_smoke(get_config("kimi-k2-1t-a32b")),
                     capacity_factor=4.0)
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32)
    out_ref, _ = moe(p, x, cfg)   # no mesh in scope -> jit oracle path
    # E-sharded: tp=2, E=4
    with jax.set_mesh(mesh):
        out_e, _ = jax.jit(lambda p, x: moe_sharded(p, x, cfg))(p, x)
    assert float(jnp.max(jnp.abs(out_ref - out_e))) < 2e-4
    # F-sharded: tp=8 > E=4
    mesh8 = jax.make_mesh((1, 8), ("data", "model"), axis_types=auto2)
    with jax.set_mesh(mesh8):
        out_f, _ = jax.jit(lambda p, x: moe_sharded(p, x, cfg))(p, x)
    assert float(jnp.max(jnp.abs(out_ref - out_f))) < 2e-4
    # batch=1 (long-context decode): dp must degrade gracefully
    x1 = x[:1]
    with jax.set_mesh(mesh):
        out_1, _ = jax.jit(lambda p, x: moe_sharded(p, x, cfg))(p, x1)
    ref_1, _ = moe(p, x1, cfg)
    assert float(jnp.max(jnp.abs(ref_1 - out_1))) < 2e-4
    print("MOE_SHARD_MAP_OK")
    print("ALL_OK")
""")


@pytest.mark.slow
def test_dryrun_small_mesh():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, cwd=ROOT, env=env, timeout=1200)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "ALL_OK" in r.stdout


def test_depth_plan_covers_all_archs():
    from repro.configs import ARCH_ALIASES, get_config
    from repro.launch import dryrun as dr
    for arch in ARCH_ALIASES:
        cfg = get_config(arch)
        l1, l2, n_units, mk = dr._depth_plan(cfg)
        assert l2 > l1 >= 1
        assert n_units > 0
        c1 = mk(l1)
        assert c1.n_layers == l1 and not c1.scan_layers


def test_model_flops_formulas():
    from repro.configs import get_config
    from repro.configs.base import SHAPES
    from repro.roofline.analysis import model_flops
    cfg = get_config("qwen2.5-14b")
    mf_train = model_flops(cfg, SHAPES["train_4k"], 256)
    # 6 * 14.77e9 * (4096*256) / 256
    assert abs(mf_train - 6 * cfg.param_count() * 4096) / mf_train < 1e-6
    mf_dec = model_flops(cfg, SHAPES["decode_32k"], 256)
    assert abs(mf_dec - 2 * cfg.param_count() * 128 / 256) / mf_dec < 1e-6
    # MoE uses active params
    moe = get_config("mixtral-8x22b")
    mf = model_flops(moe, SHAPES["train_4k"], 256)
    assert mf < 6 * moe.param_count() * 4096  # < total-param count
