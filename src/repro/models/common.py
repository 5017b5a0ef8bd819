"""Shared model building blocks (pure JAX, functional, pytree params).

Every module exposes ``init_*`` (params), ``spec_*`` (a PartitionSpec tree
mirroring the params tree: TP over ``model``, FSDP over ``data``), and an
apply function. No flax/haiku in this environment — params are plain nested
dicts, which keeps checkpointing, sharding and scanning explicit.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Params = Any  # nested dict of arrays
TP = "model"   # tensor-parallel mesh axis
FSDP = "data"  # fully-sharded-data-parallel mesh axis


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def current_mesh():
    """The mesh set by ``jax.set_mesh``, or None when no mesh (or an empty
    one) is active."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def maybe_shard(x, spec: P):
    """with_sharding_constraint that degrades to a no-op when the current
    (abstract) mesh lacks the referenced axes — so model code runs unchanged
    on a single CPU device, under tests, and under the production mesh."""
    mesh = current_mesh()
    names = set(mesh.axis_names) if mesh is not None else set()
    if not names:
        return x
    clean = []
    for s in tuple(spec):
        if s is None:
            clean.append(None)
        elif isinstance(s, tuple):
            t = tuple(a for a in s if a in names)
            clean.append(t if t else None)
        else:
            clean.append(s if s in names else None)
    return jax.lax.with_sharding_constraint(x, P(*clean))


def batch_spec():
    """Batch-dim sharding: over ('pod','data') when present."""
    return ("pod", "data")


def stack_fold(body, carry, stacked, scan: bool):
    """lax.scan over stacked layer params, or an unrolled Python loop.

    Unrolled mode exists for the dry-run's roofline analysis: XLA's
    cost_analysis counts a while-loop body ONCE regardless of trip count
    (verified empirically), so scanned stacks under-report FLOPs/bytes and
    per-layer collectives. Unrolling makes the compiled artifact's counts
    exact. Production uses scan (depth-independent HLO).
    """
    if scan:
        return jax.lax.scan(body, carry, stacked)
    n = jax.tree.leaves(stacked)[0].shape[0]
    ys = []
    for i in range(n):
        sl = jax.tree.map(lambda a: a[i], stacked)
        carry, y = body(carry, sl)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *xs: jnp.stack(xs), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------------- #
#  Initializers
# ---------------------------------------------------------------------- #
def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------- #
#  Norms (computed in fp32, cast back)
# ---------------------------------------------------------------------- #
def rms_norm(x, weight, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------- #
#  Rotary position embeddings (full-head-dim, llama-style)
# ---------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., :, None, :]               # (...,S,1,hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------- #
#  Sinusoidal positions (Whisper encoder)
# ---------------------------------------------------------------------- #
def sinusoidal_positions(n_pos: int, dim: int) -> jnp.ndarray:
    pos = np.arange(n_pos)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10_000, 2 * i / dim)
    out = np.concatenate([np.sin(angle), np.cos(angle)], axis=-1)
    return jnp.asarray(out, dtype=jnp.float32)


# ---------------------------------------------------------------------- #
#  Embedding / unembedding
# ---------------------------------------------------------------------- #
def init_embeddings(key, cfg):
    dt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    p = {"tok": embed_init(k1, (cfg.vocab_size, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, (cfg.d_model, cfg.vocab_size), dt,
                                  fan_in=cfg.d_model)
    return p


def spec_embeddings(cfg):
    # vocab-parallel over TP only. Deliberately NOT FSDP-sharding the
    # d_model dim: a gather from a table whose non-vocab dim is sharded over
    # 'data' makes GSPMD emit D-sharded/batch-REPLICATED activations, which
    # destroys batch sharding for the whole network downstream (seen as
    # full-global-batch all-gathers in the dry-run HLO).
    p = {"tok": P(TP, None)}
    if not cfg.tie_embeddings:
        p["unembed"] = P(FSDP, TP)
    return p


def embed_tokens(params, tokens, cfg):
    out = jnp.take(params["tok"], tokens, axis=0)
    out = out.astype(dtype_of(cfg.activation_dtype))
    # pin the canonical activation layout at network entry:
    # batch over (pod, data), everything else replicated
    return maybe_shard(out, P(("pod", FSDP), None, None))


def unembed(params, x, cfg):
    w = params.get("unembed")
    if w is None:
        w = params["tok"].T
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    # vocab-parallel logits: the (B, S, V) fp32 tensor dominates activation
    # memory at 50k-160k vocabs; keep V sharded over TP — the loss's
    # logsumexp reduces over the sharded axis with one small all-reduce
    return maybe_shard(logits, P(("pod", FSDP), None, TP))
