"""Serve a model with batched requests: prefill + autoregressive decode
with the KV cache (ring-buffer windowed cache for SWA archs). On a TPU the
model runs at its published width; on the CPU it runs a reduced copy.

Run:  PYTHONPATH=src python examples/serve_decode.py [--arch mixtral-8x22b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import reduce_for_smoke
from repro.core.model_apps import derive_app
from repro.launch.compile_cache import use_compile_cache
from repro.models import model
from repro.train.serve import greedy_generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x22b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    use_compile_cache()
    platform = jax.default_backend()
    cfg = get_config(args.arch)
    if platform == "cpu":
        cfg = reduce_for_smoke(cfg)
    print(f"arch={cfg.name} family={cfg.family} platform={platform}")
    for phase in ("prefill", "decode"):
        app = derive_app(args.arch, phase)
        print(f"scheduler app: {app.name} (flops={app.flops:.3g} "
              f"hbm={app.hbm_bytes:.3g}B n_chips={app.n_chips}, "
              f"full-size counters the DVFS scheduler dispatches on)")
    params = model.init(cfg, jax.random.PRNGKey(0))

    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)
    max_seq = args.prompt_len + args.gen + 8

    t0 = time.time()
    out = greedy_generate(cfg, params, prompt, n_steps=args.gen,
                          max_seq=max_seq).block_until_ready()
    dt = time.time() - t0
    print(f"prefill({args.batch}x{args.prompt_len}) + decode {args.gen} "
          f"steps in {dt:.1f}s, compilation included "
          f"({args.batch*args.gen/dt:.1f} tok/s on {platform})")
    print("generated token ids (first request):", out[0].tolist())

    # consistency: teacher-forcing forward over prompt+generated reproduces
    # the same greedy continuation
    full = jnp.concatenate([prompt[:1], out[:1]], axis=1)
    Stext = model.text_len(cfg, full.shape[1])
    logits, _ = model.forward(cfg, params, full[:, :Stext],
                              model.extra_inputs(cfg, 1, full.shape[1]))
    redo = jnp.argmax(logits[0, args.prompt_len - 1:-1], axis=-1)
    agree = float(jnp.mean((redo == out[0]).astype(jnp.float32)))
    print(f"teacher-forcing agreement with decode path: {100*agree:.0f}%")


if __name__ == "__main__":
    main()
