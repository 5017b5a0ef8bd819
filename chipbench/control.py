"""The control of the comparison that decides ``correct``, and the faults
it must catch.

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --mode <program|control|half|altered|stale|sheddable> \\
        --seeds <n> [<n> ...]

runs the cell once per seed in one process and prints each run's compared
numbers; the benchmark's own runs never run this. The modes:

* ``program``: the program as it is (the lower readings).
* ``control``: the GBDT kernel's feature gather one precision step below
  the program's. The kernel gathers features with an f32 matmul at
  ``Precision.HIGHEST``; the next step down is ``HIGH`` (three bf16
  passes). Mosaic compiles only DEFAULT and HIGHEST, so the control kernel
  writes HIGH out: the features split into a bf16 high and low part, each
  multiplied by the bf16 one-hot selector. The selector is exact in bf16,
  so the third pass of HIGH is zero and this is HIGH exactly.
* ``half``: the kernel's leaf indices for the second half of each batch
  left out (zero).
* ``altered``: one leaf index of each batch altered where the kernel
  produces it.
* ``stale``: the facility coordinator's ``commit`` returns with its state
  unchanged.
* ``sheddable``: admission control sees every job's tier as sheddable
  (cells whose configuration has ``admission``).

The one-chip cells have no exchange between chips to leave out.
"""
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _high_kernel(x_ref, oh_ref, thr_ref, idx_ref, *, depth: int):
    import jax
    import jax.numpy as jnp

    x = x_ref[...]
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    idx = jnp.zeros(idx_ref.shape, jnp.int32)
    for d in range(depth):
        oh = oh_ref[d].astype(jnp.bfloat16)
        g = (jax.lax.dot_general(hi, oh, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(lo, oh, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
        idx = idx + jnp.where(g > thr_ref[d:d + 1, :], 1 << d, 0)
    idx_ref[...] = idx


@functools.lru_cache(maxsize=None)
def high_leaf_indices():
    """The program's ``gbdt_leaf_indices`` with the gather at HIGH."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @functools.partial(jax.jit, static_argnames=("interpret", "bn", "bt"))
    def leaf_indices(X, feats_onehot, thresholds, interpret=False,
                     bn=256, bt=128):
        n, F = X.shape
        depth, T = thresholds.shape
        return pl.pallas_call(
            functools.partial(_high_kernel, depth=depth),
            grid=(n // bn, T // bt),
            in_specs=[
                pl.BlockSpec((bn, F), lambda ni, ti: (ni, 0)),
                pl.BlockSpec((depth, F, bt), lambda ni, ti: (0, 0, ti)),
                pl.BlockSpec((depth, bt), lambda ni, ti: (0, ti)),
            ],
            out_specs=pl.BlockSpec((bn, bt), lambda ni, ti: (ni, ti)),
            out_shape=jax.ShapeDtypeStruct((n, T), jnp.int32),
            interpret=interpret,
        )(X, feats_onehot, thresholds)

    return leaf_indices


def install(mode: str):
    """Break the timed path for ``mode``; returns the ``fault(service,
    coordinator, admission)`` hook for :func:`chipbench.harness.run_cell`
    (or None) and an undo function."""
    import dataclasses

    import numpy as np

    from repro.kernels import gbdt_predict, ops

    if mode == "program":
        return None, lambda: None
    if mode == "control":
        orig = gbdt_predict.gbdt_leaf_indices
        gbdt_predict.gbdt_leaf_indices = high_leaf_indices()
        return None, lambda: setattr(gbdt_predict, "gbdt_leaf_indices", orig)
    if mode in ("half", "altered"):
        orig = ops.gbdt_leaf_indices

        def broken(X, feats, thresholds, **kw):
            idx = np.array(orig(X, feats, thresholds, **kw))
            if mode == "half":
                idx[idx.shape[0] // 2:] = 0
            else:
                idx[0, 0] ^= 1
            return idx

        ops.gbdt_leaf_indices = broken
        return None, lambda: setattr(ops, "gbdt_leaf_indices", orig)
    if mode == "stale":
        def fault(_service, coord, _admission):
            coord.commit = lambda *a, **kw: None
        return fault, lambda: None
    if mode == "sheddable":
        def fault(_service, _coord, adm):
            if adm is None:
                raise ValueError("the sheddable fault needs a configuration "
                                 "with admission")
            check = adm.check

            def every_tier_sheddable(job, now, queue):
                tier = dataclasses.replace(job.tier, sheddable=True)
                return check(dataclasses.replace(job, tier=tier), now,
                             queue)

            adm.check = every_tier_sheddable
        return fault, lambda: None
    raise ValueError(f"unknown mode {mode!r}")


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--mode", default="program",
                   choices=("program", "control", "half", "altered",
                            "stale", "sheddable"))
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    a = p.parse_args(argv)

    import jax

    from chipbench import harness
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(a.workload)
    fault, undo = install(a.mode)
    try:
        for seed in a.seeds:
            out = harness.run_cell(cell, seed, a.seconds, False,
                                   time.perf_counter(), fault=fault)
            print(json.dumps({"mode": a.mode, "seed": seed,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "checks": {k: c["value"] for k, c
                                         in out["checks"].items()}}),
                  flush=True)
    finally:
        undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
