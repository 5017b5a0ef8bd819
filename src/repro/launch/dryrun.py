import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede every other import: jax locks the device count on first
# initialization. This flag exists ONLY here (smoke tests/benches see 1 CPU).

"""Multi-pod dry-run: AOT-lower + compile every (architecture x input-shape)
cell on the production meshes, print memory_analysis / cost_analysis, and
cache the roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
      --shape train_4k [--multi-pod] [--out out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Every failure here (sharding mismatch, non-divisible dims, unsupported
collective) is a bug in the distribution config — the dry-run is the proof
the system is launchable at 512 chips.
"""
import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_ALIASES, get_config
from repro.configs.base import SHAPES, ShapeSpec, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.models.common import dtype_of
from repro.optim import adamw
from repro.roofline import analysis as roofline
from repro.train.serve import make_serve_step
from repro.train.step import make_train_step

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


# ---------------------------------------------------------------------- #
#  Sharding utilities
# ---------------------------------------------------------------------- #
def _axis_size(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop spec entries whose mesh axes don't exist or don't divide the
    dim (e.g. whisper's vocab 51866 % 16 != 0 → vocab unsharded)."""
    out = []
    for i, entry in enumerate(tuple(spec)):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        names = tuple(a for a in names if a in mesh.shape)
        # greedy prefix of axes that divides the dim
        kept = []
        size = 1
        for a in names:
            if shape[i] % (size * mesh.shape[a]) == 0:
                kept.append(a)
                size *= mesh.shape[a]
            else:
                break
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def named(mesh, spec_tree, shape_tree):
    """spec tree + eval_shape tree → NamedSharding tree (sanitized)."""
    is_spec = lambda s: isinstance(s, P)
    return jax.tree.map(
        lambda s, sh: NamedSharding(mesh, sanitize_spec(s, sh.shape, mesh)),
        spec_tree, shape_tree, is_leaf=is_spec)


def podify(spec_tree):
    """Batch/cache spec trees: extend the 'data' axis to ('pod','data') so
    decode/serve inputs shard across pods too (params stay pod-replicated —
    pure DP over DCN)."""
    is_spec = lambda s: isinstance(s, P)

    def one(s):
        out = []
        for entry in tuple(s):
            if entry == "data":
                out.append(("pod", "data"))
            elif isinstance(entry, tuple) and "data" in entry:
                out.append(("pod",) + tuple(entry))
            else:
                out.append(entry)
        return P(*out)

    return jax.tree.map(one, spec_tree, is_leaf=is_spec)


# ---------------------------------------------------------------------- #
#  input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------- #
def input_specs(cfg, shape: ShapeSpec) -> dict:
    """ShapeDtypeStructs for the *data* inputs of the lowered step."""
    B, S = shape.global_batch, shape.seq_len
    act = dtype_of(cfg.activation_dtype)
    if shape.mode == "train":
        S_text = model_lib.text_len(cfg, S)
        d = {
            "tokens": jax.ShapeDtypeStruct((B, S_text), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S_text), jnp.int32),
        }
        if cfg.family == "vlm":
            d["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.d_model), act)
        if cfg.family == "audio":
            d["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), act)
        return d
    if shape.mode == "prefill":
        S_text = model_lib.text_len(cfg, S)
        d = {"tokens": jax.ShapeDtypeStruct((B, S_text), jnp.int32)}
        if cfg.family == "vlm":
            d["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.d_model), act)
        if cfg.family == "audio":
            d["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), act)
        return d
    # decode: one new token against a seq_len KV cache
    return {
        "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def batch_specs(cfg, shape: ShapeSpec) -> dict:
    dp = ("pod", "data")
    if shape.mode in ("train", "prefill"):
        d = {"tokens": P(dp, None)}
        if shape.mode == "train":
            d["labels"] = P(dp, None)
        if cfg.family == "vlm":
            d["vision_embeds"] = P(dp, None, None)
        if cfg.family == "audio":
            d["frames"] = P(dp, None, None)
        return d
    return {"tokens": P(dp, None), "pos": P()}


# ---------------------------------------------------------------------- #
def _depth_plan(cfg):
    """(l1, l2, n_units, field) for linear-in-depth cost extrapolation.

    Unrolled compiles at depths l1 < l2 give exact per-unit costs (XLA's
    cost model counts while-loop bodies once, so the production *scanned*
    compile under-reports; see roofline/analysis.py). hybrid compiles at
    whole-period depths, but the slope — like every family's — is PER LAYER
    and n_units is the layer count (the shared attn block rides along at
    1/period per layer: 81/6 = 13.5 vs 13 true applications, ≈3.8%
    overcount of that block, documented); audio scales enc+dec together."""
    import dataclasses as dc
    if cfg.family == "hybrid":
        p = cfg.hybrid_attn_period
        return (p, 2 * p, cfg.n_layers,
                lambda n: dc.replace(cfg, n_layers=n, scan_layers=False))
    if cfg.family == "audio":
        return (1, 2, cfg.n_layers,
                lambda n: dc.replace(cfg, n_layers=n, n_encoder_layers=n,
                                     scan_layers=False))
    if cfg.family == "moe" and cfg.first_dense_layers:
        d = cfg.first_dense_layers
        return (d + 1, d + 2, cfg.n_layers - d,
                lambda n: dc.replace(cfg, n_layers=n, scan_layers=False))
    return (1, 2, cfg.n_layers,
            lambda n: dc.replace(cfg, n_layers=n, scan_layers=False))


def podify_fsdp(spec_tree):
    """ZeRO-3 over DCN: extend every FSDP ('data') entry in the param/opt
    specs to ('data','pod') — used when cfg.fsdp_over_pod (Kimi-K2: 1T
    params cannot fit 2 pods with pod-replicated state)."""
    is_spec = lambda s: isinstance(s, P)

    def one(s):
        out = []
        for entry in tuple(s):
            if entry == "data":
                out.append(("data", "pod"))
            elif isinstance(entry, tuple) and "data" in entry and \
                    "pod" not in entry:
                out.append(tuple(entry) + ("pod",))
            else:
                out.append(entry)
        return P(*out)

    return jax.tree.map(one, spec_tree, is_leaf=is_spec)


def _build_jitted(cfg, shape, mesh, microbatches):
    params_shapes = jax.eval_shape(
        functools.partial(model_lib.init, cfg), jax.random.PRNGKey(0))
    p_specs = model_lib.param_specs(cfg)
    if cfg.fsdp_over_pod and "pod" in mesh.shape:
        p_specs = podify_fsdp(p_specs)
    p_shardings = named(mesh, p_specs, params_shapes)
    data = input_specs(cfg, shape)
    b_specs = batch_specs(cfg, shape)

    if shape.mode == "train":
        ocfg = adamw.AdamWConfig(state_dtype=cfg.opt_state_dtype)
        opt_shapes = jax.eval_shape(
            functools.partial(adamw.init, cfg=ocfg), params_shapes)
        o_specs = adamw.state_specs(p_specs, jax.tree.map(
            lambda x: x.shape, params_shapes,
            is_leaf=lambda x: hasattr(x, "shape")), ocfg)
        o_shardings = named(mesh, o_specs, opt_shapes)
        b_shardings = named(mesh, b_specs, data)
        step = make_train_step(cfg, ocfg, microbatches=microbatches)
        jitted = jax.jit(
            step,
            in_shardings=(p_shardings, o_shardings, b_shardings),
            out_shardings=(p_shardings, o_shardings, None),
            donate_argnums=(0, 1),
        )
        args = (params_shapes, opt_shapes, data)
    elif shape.mode == "decode":
        cache_shapes = jax.eval_shape(
            functools.partial(model_lib.init_cache, cfg,
                              shape.global_batch, shape.seq_len))
        c_specs = podify(model_lib.cache_specs(cfg))
        c_shardings = named(mesh, c_specs, cache_shapes)
        b_shardings = named(mesh, b_specs, data)
        serve = make_serve_step(cfg)
        jitted = jax.jit(
            serve,
            in_shardings=(p_shardings, c_shardings,
                          b_shardings["tokens"], b_shardings["pos"]),
            out_shardings=(None, c_shardings),
            donate_argnums=(1,),
        )
        args = (params_shapes, cache_shapes, data["tokens"], data["pos"])
    else:  # prefill
        from repro.train.serve import make_prefill_step
        prefill = make_prefill_step(cfg, max_seq=shape.seq_len)
        b_shardings = named(mesh, b_specs, data)
        extra_keys = [k for k in data if k != "tokens"]
        jitted = jax.jit(
            prefill,
            in_shardings=(p_shardings, b_shardings["tokens"],
                          {k: b_shardings[k] for k in extra_keys}),
        )
        args = (params_shapes, data["tokens"],
                {k: data[k] for k in extra_keys})
    return jitted, args


def _compile(cfg, shape, mesh, microbatches):
    jitted, args = _build_jitted(cfg, shape, mesh, microbatches)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatches: int = 1, remat: str = None,
             opt_override: str = None, verbose: bool = True,
             analyze_costs: bool = True, cfg_override=None) -> dict:
    import dataclasses as dc
    cfg = cfg_override or get_config(arch)
    if remat is not None:
        cfg = dc.replace(cfg, remat=remat)
    if opt_override is not None:
        cfg = dc.replace(cfg, opt_state_dtype=opt_override)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))

    # 1) production (scanned) compile: launchability + per-device memory
    t0 = time.time()
    compiled = _compile(cfg, shape, mesh, microbatches)
    t_full = time.time() - t0
    mem = roofline.memory_stats(compiled)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips, "status": "ok",
        "compile_s": round(t_full, 1),
        "params_b": cfg.param_count() / 1e9,
        "active_params_b": cfg.active_param_count() / 1e9,
        "microbatches": microbatches,
        "memory_per_device": mem,
        "fits_hbm": mem["total_bytes"] < 16e9,
        "memory_analysis": str(compiled.memory_analysis()),
        "cost_analysis_scanned": {
            k: v for k, v in compiled.cost_analysis().items()
            if k in ("flops", "bytes accessed")},
    }
    if verbose:
        print(f"[{arch} / {shape_name} / {result['mesh']}] "
              f"compile={t_full:.0f}s "
              f"mem/dev={mem['total_bytes']/1e9:.2f}GB "
              f"fits={result['fits_hbm']}")
        print(f"  memory_analysis: {result['memory_analysis']}")

    # 2) roofline costs via unrolled depth-extrapolation (single-pod table)
    if analyze_costs:
        l1, l2, n_units, mk = _depth_plan(cfg)
        t1 = time.time()
        # microbatches=1 for cost compiles: the microbatch scan body is also
        # counted once by XLA; a single full-batch pass has identical totals
        c1 = roofline.costs_of(_compile(mk(l1), shape, mesh, 1))
        c2 = roofline.costs_of(_compile(mk(l2), shape, mesh, 1))
        costs = roofline.extrapolate_costs(c1, c2, l1, l2, n_units)
        extra_f, extra_b = roofline.ssm_scan_correction(cfg, shape, n_chips)
        costs["flops"] += extra_f
        costs["bytes"] += extra_b
        mf = roofline.model_flops(cfg, shape, n_chips)
        rl = roofline.make_roofline(
            costs["flops"], costs["bytes"], costs["coll_raw"],
            costs["coll_modeled"], costs["coll_counts"], mem, mf)
        result["roofline"] = rl.to_dict()
        result["analysis_compile_s"] = round(time.time() - t1, 1)
        if verbose:
            print(f"  cost_analysis (depth-extrapolated): "
                  f"flops={rl.flops:.3e} bytes={rl.bytes_accessed:.3e} "
                  f"coll={rl.coll_bytes_modeled:.3e}B")
            print(f"  roofline: compute={rl.compute_s:.4f}s "
                  f"memory={rl.memory_s:.4f}s coll={rl.collective_s:.4f}s "
                  f"→ {rl.dominant}-bound; useful={rl.useful_ratio:.2f}")
            print(f"  collectives: {rl.coll_counts}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCH_ALIASES), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--opt-dtype", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-analysis", action="store_true",
                    help="launchability compile only (multi-pod pass; the "
                         "roofline table is single-pod per the spec)")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in sorted(ARCH_ALIASES):
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = []
    for arch, shape in cells:
        try:
            r = run_cell(arch, shape, args.multi_pod,
                         microbatches=args.microbatches, remat=args.remat,
                         opt_override=args.opt_dtype,
                         analyze_costs=not args.no_analysis)
        except Exception as e:
            traceback.print_exc()
            r = {"arch": arch, "shape": shape, "status": "error",
                 "error": f"{type(e).__name__}: {e}"}
        results.append(r)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = len(results) - n_ok - n_skip
    print(f"\nDRY-RUN SUMMARY: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
