"""Chip smoke run: the scheduler's prediction path and SmolLM-360M serving,
each driven once on one TPU chip through the entry points a user calls.

Run from the repository root on a host with a TPU::

    python chip_smoke.py

Phases, all in this one process:

(a) Scheduler at fleet scale. Profile the paper suite, fit the predictor,
    register the model-derived apps, and schedule a 10,000-job
    ``multi_rack_workload`` stream over a 64-device, 8-rack pool under a
    ``FacilityCoordinator``, with a default ``PredictionService`` whose
    tables start cold: admission waves build them through the Pallas GBDT
    kernel. The same stream on a numpy-path service is the reference:
    every table must agree within ``TABLE_RTOL``, misses and shed must be
    equal, and total energy must agree within ``ENERGY_RTOL``.
(b) SmolLM-360M at its published width (32 layers, d=960, vocab 49152,
    bf16) with random weights from a seed: 4 requests, a 512-token
    prefill and 32 greedy decode steps through the compiled Pallas flash
    attention kernel. Prefill logits must agree with the XLA attention
    path within ``LOGITS_RTOL``.

The last line of standard output is one JSON object naming the device. On
a host without a TPU the script exits non-zero before any phase runs; a
failed check raises. The times printed include compilation: this is a
smoke run, not a benchmark.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.bench_federation import (CAP_FRAC, FULL_POOL,  # noqa: E402
                                         FULL_RACKS, GUARD, UTIL)
from benchmarks.common import fixtures  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import (FacilityCoordinator, PowerTelemetry,  # noqa: E402
                        PredictionService, RiskAware, Testbed, V5E_DVFS,
                        make_device_pool, model_app_suite,
                        multi_rack_workload, register_model_apps,
                        run_schedule)
from repro.core.model_apps import (DECODE_SHAPE, DECODE_STEPS,  # noqa: E402
                                   aot_counters, derive_counters)
from repro.kernels import gbdt_predict as gbdt_kernel  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import model  # noqa: E402
from repro.train.serve import (greedy_decode_step,  # noqa: E402
                               greedy_generate, prefill)

#: Largest relative difference allowed between a kernel-built ladder table
#: and the numpy table (the kernel picks leaves, the host sums them in
#: float64 as numpy does, so equal leaf choices give equal tables).
TABLE_RTOL = 1e-4
#: Total schedule energy, kernel-path service vs numpy-path service.
ENERGY_RTOL = 1e-4
#: Relative L2 error of flash-attention prefill logits against the XLA
#: attention path, both in bf16 (2**-8 = 3.9e-3 per rounding), after 32
#: layers of bf16 residual stream.
LOGITS_RTOL = 5e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _rel(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.max(np.abs(a - b) / np.abs(b)))


def scheduler_phase(pool, racks, n_jobs: int, seed: int = 0) -> None:
    """Phase (a): kernel-path vs numpy-path schedule of one stream."""
    t0 = time.perf_counter()
    f = fixtures()
    tb = f["testbed"]
    feats = {**f["features"], **register_model_apps(None, tb)}
    apps = list(f["apps"]) + list(model_app_suite())
    jobs = list(multi_rack_workload(apps, tb, n_jobs=n_jobs, seed=seed,
                                    utilization=UTIL, device_classes=pool))

    def service(**kw) -> PredictionService:
        return PredictionService(V5E_DVFS, predictor=f["predictor"],
                                 app_features=dict(feats), testbed=tb, **kw)

    def schedule(svc, cap_w=None):
        fac = (None if cap_w is None
               else FacilityCoordinator(cap_w, racks, guard=GUARD))
        return run_schedule(jobs, RiskAware(V5E_DVFS, margin=0.05),
                            Testbed(seed=100 + seed), service=svc,
                            device_classes=pool, power_coordinator=fac)

    # the numpy-path reference also sizes the facility cap: idle floor +
    # CAP_FRAC of the uncapped peak above it
    ref = service(use_kernel=False)
    peak = PowerTelemetry.from_result(schedule(ref), pool=pool).peak_w
    floor = sum(c.idle_power() for c in pool)
    cap_w = floor + CAP_FRAC * (peak - floor)
    setup_s = time.perf_counter() - t0

    svc = service()
    t1 = time.perf_counter()
    got = schedule(svc, cap_w)
    run_s = time.perf_counter() - t1
    want = schedule(ref, cap_w)
    st = dataclasses.replace(svc.stats)

    classes = list({c.name: c for c in pool}.values())
    worst = 0.0
    for app in apps:
        for cls in classes:
            a, b = svc.base_table(app.name, cls), ref.base_table(app.name, cls)
            worst = max(worst, _rel(a.P, b.P), _rel(a.T, b.T))
    differ = sum(x != y for x, y in zip(got.records, want.records))
    differ += abs(len(got.records) - len(want.records))
    e_rel = abs(got.total_energy - want.total_energy) / want.total_energy

    print(f"(a) scheduler smoke run, not a benchmark: {n_jobs} jobs, "
          f"{len(pool)} devices, {len(racks)} racks, {len(apps)} apps, "
          f"facility cap {cap_w:.1f} W")
    print(f"(a) rows predicted {st.rows_predicted}, kernel batches "
          f"{st.kernel_batches}, distinct kernel shapes compiled "
          f"{gbdt_kernel.gbdt_leaf_indices._cache_size()}, tables built "
          f"{st.table_builds}")
    print(f"(a) set-up {setup_s:.3f} s (profiling, fit, reference runs), "
          f"kernel-path run {run_s:.3f} s, compilation included")
    print(f"(a) max relative table difference vs numpy {worst:.3e} "
          f"(bound {TABLE_RTOL:g})")
    print(f"(a) misses {got.misses} vs {want.misses}, shed "
          f"{got.shed_count} vs {want.shed_count}, energy "
          f"{got.total_energy:.6e} J vs {want.total_energy:.6e} J "
          f"(relative {e_rel:.3e}, bound {ENERGY_RTOL:g}), records that "
          f"differ {differ} of {len(want.records)}")
    check(st.kernel_batches > 0, "no batch ran through the GBDT kernel")
    check(worst <= TABLE_RTOL, f"table difference {worst:.3e}")
    check(got.misses == want.misses, "misses differ from the reference")
    check(got.shed_count == want.shed_count, "shed differs")
    check(e_rel <= ENERGY_RTOL, f"energy difference {e_rel:.3e}")


def serving_phase(cfg, batch: int, prompt_len: int, gen: int,
                  seed: int = 0) -> None:
    """Phase (b): greedy serving through the flash kernel, prefill logits
    checked against the XLA attention path."""
    t0 = time.perf_counter()
    params = model.init(cfg, jax.random.PRNGKey(seed))
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab_size)
    max_seq = prompt_len + gen
    flash = dataclasses.replace(cfg, attn_impl="flash")
    xla = dataclasses.replace(cfg, attn_impl="xla")

    out = greedy_generate(flash, params, prompt, n_steps=gen,
                          max_seq=max_seq)
    out = np.asarray(out)
    serve_s = time.perf_counter() - t0
    check(out.shape == (batch, gen), f"generated shape {out.shape}")
    check(bool(np.all((out >= 0) & (out < cfg.vocab_size))),
          "generated token ids out of range")
    has_kernel = "tpu_custom_call" in prefill.lower(
        flash, params, prompt, max_seq).as_text()

    lf, _ = prefill(flash, params, prompt, max_seq)
    lx, _ = prefill(xla, params, prompt, max_seq)
    finite = bool(jnp.all(jnp.isfinite(lf)) & jnp.all(jnp.isfinite(lx)))
    rel = float(jnp.linalg.norm(lf - lx) / jnp.linalg.norm(lx))
    max_abs = float(jnp.max(jnp.abs(lf - lx)))

    # XLA's cost analysis counts a scanned layer body once, so the decode
    # step whose costs are read is compiled with its layers unrolled
    cache = jax.eval_shape(lambda: model.init_cache(cfg, batch, max_seq))
    step = greedy_decode_step.lower(
        dataclasses.replace(flash, scan_layers=False), params, cache,
        jax.ShapeDtypeStruct((batch, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    aot = aot_counters(step)
    analytic = derive_counters(get_config("smollm_360m"), "decode")
    app_tokens = DECODE_SHAPE.global_batch * DECODE_STEPS

    print(f"(b) serving smoke run, not a benchmark: {cfg.name} "
          f"{cfg.n_layers} layers d={cfg.d_model} vocab={cfg.vocab_size} "
          f"{cfg.param_dtype}, {batch} requests, prefill {prompt_len} "
          f"tokens + {gen} greedy steps in {serve_s:.3f} s, compilation "
          f"included")
    print(f"(b) flash kernel in the prefill program: {has_kernel}; "
          f"generated ids (request 0): {out[0].tolist()}")
    print(f"(b) prefill logits flash vs xla: relative L2 {rel:.3e} "
          f"(bound {LOGITS_RTOL:g}), max abs {max_abs:.3e}, "
          f"finite {finite}")
    print(f"(b) smollm_360m:decode counters, step compiled for "
          f"{jax.default_backend()} "
          f"(batch {batch}, cache {max_seq}, one step): "
          + ("none" if aot is None else
             f"flops {aot[0]:.4e}, bytes {aot[1]:.4e}, flops per token "
             f"{aot[0] / batch:.4e}")
          + f"; analytic app (batch {DECODE_SHAPE.global_batch}, seq "
          f"{DECODE_SHAPE.seq_len}, {DECODE_STEPS} steps, per chip of "
          f"{analytic['n_chips']}): flops {analytic['flops']:.4e}, bytes "
          f"{analytic['hbm_bytes']:.4e}, flops per token "
          f"{analytic['flops'] * analytic['n_chips'] / app_tokens:.4e}")
    check(has_kernel, "prefill program holds no Pallas kernel")
    check(finite, "non-finite prefill logits")
    check(rel <= LOGITS_RTOL, f"flash vs xla logits {rel:.3e}")
    check(aot is not None, "compiled decode step reports no costs")


def main() -> int:
    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    scheduler_phase(make_device_pool(*FULL_POOL), list(FULL_RACKS),
                    n_jobs=10_000)
    serving_phase(get_config("smollm_360m"), batch=4, prompt_len=512,
                  gen=32)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
