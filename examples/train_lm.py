"""Train a small LM for a few hundred steps with the full substrate:
synthetic data pipeline, AdamW, remat, async checkpointing, fault-tolerant
runner with an injected failure + bit-exact restart.

Default: a ~55M-param llama-style model (SmolLM family), 200 steps on CPU.
Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200] [--dim 512]
"""
import argparse
import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.model_apps import derive_app
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist.fault_tolerance import (FailureInjector, RunnerConfig,
                                        TrainingRunner)
from repro.launch.compile_cache import use_compile_cache
from repro.models import model
from repro.optim import adamw
from repro.train.step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--inject-failure", action="store_true", default=True)
    args = ap.parse_args()
    use_compile_cache()

    cfg = dataclasses.replace(
        get_config("smollm-360m"),
        n_layers=args.layers, d_model=args.dim, n_heads=8, n_kv_heads=4,
        head_dim=args.dim // 8, d_ff=args.dim * 4, vocab_size=args.vocab,
        param_dtype="float32", activation_dtype="float32", remat="none")
    n_params = cfg.param_count()
    print(f"model: {args.layers}L d={args.dim} vocab={args.vocab} "
          f"→ {n_params/1e6:.1f}M params")
    app = derive_app("smollm-360m", "train_step")
    print(f"scheduler app: {app.name} (flops={app.flops:.3g} "
          f"hbm={app.hbm_bytes:.3g}B coll={app.coll_bytes:.3g}B "
          f"n_chips={app.n_chips}, full-size counters the DVFS "
          f"scheduler dispatches on)")

    params = model.init(cfg, jax.random.PRNGKey(0))
    ocfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=20,
                             total_steps=args.steps, weight_decay=0.01)
    opt = adamw.init(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg))

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch, seed=0,
                                  order=1))

    # keyed by step so checkpoint-restart replays overwrite, not duplicate
    history = {}
    cur_step = {"s": 0}

    def data_fn(s):
        cur_step["s"] = s
        return {k: jnp.asarray(v) for k, v in data.batch(s).items()}

    def step_fn(p, o, batch):
        p, o, m = step(p, o, batch)
        history[cur_step["s"]] = float(m["loss"])
        return p, o, m

    ckpt_dir = tempfile.mkdtemp(prefix="repro_train_lm_")
    injector = FailureInjector(fail_at=(args.steps // 2,)) \
        if args.inject_failure else None
    runner = TrainingRunner(
        RunnerConfig(ckpt_dir=ckpt_dir, ckpt_interval=50),
        step_fn, data_fn, injector=injector)

    t0 = time.time()
    params, opt, _ = runner.run(params, opt, 0, args.steps)
    dt = time.time() - t0
    losses = [history[s] for s in sorted(history)]
    first = np.mean(losses[:10])
    last = np.mean(losses[-10:])
    tok_s = args.batch * args.seq * len(losses) / dt
    print(f"steps={len(losses)} restarts={runner.restarts} wall={dt:.0f}s "
          f"({tok_s:.0f} tok/s)")
    print(f"loss: {first:.3f} → {last:.3f} "
          f"(uniform = {np.log(args.vocab):.3f})")
    assert last < first - 0.2, "loss did not improve"
    print("OK: loss decreased; failure was injected and recovered" if
          runner.restarts else "OK: loss decreased")


if __name__ == "__main__":
    main()
