"""Compile the Pallas kernels for a described TPU v5e at the widths the
system runs them, without a chip: the TPU compiler refuses layouts, tilings
and VMEM use that interpret mode accepts. Each compiled program must hold
the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and tests that exist on one
xdist worker must exist on every worker.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import gbdt_predict as gp
from repro.kernels import mamba_scan as ms


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip; keep such entries out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, shapes, **static):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return fn.lower(*args, interpret=False, **static).compile().as_text()


def test_gbdt_leaf_indices_predictor_size(one_chip):
    """Two ensembles of 400 depth-4 trees (padded to 512 lanes) over the
    23 DVFS features, 4096 rows."""
    f32 = jnp.float32
    text = _compiled_text(one_chip, gp.gbdt_leaf_indices,
                          [((4096, 23), f32), ((4, 23, 512), f32),
                           ((4, 512), f32)])
    assert "tpu_custom_call" in text


def test_gbdt_leaf_indices_custom_call_name(one_chip):
    """The kernel's custom call keeps the name the benchmark's trace
    reduction matches, ``gbdt_leaf_indices``."""
    f32 = jnp.float32
    text = _compiled_text(one_chip, gp.gbdt_leaf_indices,
                          [((1024, 23), f32), ((4, 23, 512), f32),
                           ((4, 512), f32)])
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(", text)
    assert any(c.startswith("gbdt_leaf_indices") for c in calls), calls


def test_flash_attention_smollm_heads(one_chip):
    """SmolLM-360M attention: 15 query / 5 kv heads, head dim 64, 2048
    tokens, bf16."""
    bf16 = jnp.bfloat16
    text = _compiled_text(one_chip, fa.flash_attention,
                          [((1, 15, 2048, 64), bf16), ((1, 5, 2048, 64), bf16),
                           ((1, 5, 2048, 64), bf16)], causal=True)
    assert "tpu_custom_call" in text


def test_mamba_scan_falcon_mamba_width(one_chip):
    """falcon-mamba-7b's scan: d_inner 8192, state 16, 1024 tokens."""
    f32 = jnp.float32
    B, L, Di, N = 1, 1024, 8192, 16
    text = _compiled_text(one_chip, ms.mamba_scan,
                          [((B, L, Di), f32), ((B, L, Di), f32),
                           ((Di, N), f32), ((B, L, N), f32),
                           ((B, L, N), f32), ((Di,), f32)])
    assert "tpu_custom_call" in text
