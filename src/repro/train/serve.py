"""Serving steps: prefill and batched decode (the dry-run's serve_step),
and the jitted greedy serving loop the examples and ``chip_smoke.py`` run."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import model as model_lib


def make_serve_step(cfg):
    """decode_step(params, cache, tokens (B,1), pos) → (logits, cache).
    This is what ``decode_*`` / ``long_*`` shapes lower (one new token
    against a KV cache of seq_len)."""

    def serve_step(params, cache, tokens, pos):
        return model_lib.decode_step(cfg, params, cache, tokens, pos)

    return serve_step


def make_prefill_step(cfg, max_seq: int):
    def prefill_step(params, tokens, extra):
        return model_lib.prefill(cfg, params, tokens, max_seq, extra)

    return prefill_step


@functools.partial(jax.jit, static_argnums=(0, 3))
def prefill(cfg, params, prompt, max_seq: int, extra=None):
    """Jitted prefill: (logits (B, S, V) fp32, KV cache of ``max_seq``)."""
    return model_lib.prefill(cfg, params, prompt, max_seq, extra)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def greedy_decode_step(cfg, params, cache, tok, pos):
    """One jitted greedy decode step; the cache is donated and updated in
    place. Returns (next token (B, 1) int32, cache)."""
    logits, cache = model_lib.decode_step(cfg, params, cache, tok, pos)
    return jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), cache


def greedy_generate(cfg, params, prompt, n_steps: int, max_seq: int,
                    extra=None):
    """Greedy autoregressive serving loop: one jitted prefill, then
    ``n_steps - 1`` jitted decode steps. Returns (B, n_steps) int32."""
    logits, cache = prefill(cfg, params, prompt, max_seq, extra)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    pos0 = prompt.shape[1] + (cfg.vision_tokens if cfg.family == "vlm" else 0)
    for i in range(n_steps - 1):
        tok, cache = greedy_decode_step(cfg, params, cache, tok,
                                        jnp.int32(pos0 + i))
        out.append(tok)
    return jnp.concatenate(out, axis=1)
