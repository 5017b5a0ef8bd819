"""Decision-core throughput benchmark: scalar vs vectorized dispatch.

ROADMAP names the per-decision Python scan as the scheduler's hot path at
stream scale; PR 6 replaces it with the vectorized decision core
(:mod:`repro.core.batch_decide`) — compiled selection ladders, stacked
joint scoring, batched ladder prefetch, and the cached measurement
substrate — keeping the scalar path as the small-N fallback and the
bit-identity oracle. This bench measures exactly that trade on 100k-job /
8-device streams (2k-job copies for the CI smoke gate), in four scenarios:

* ``uniform``       — classless 8×v5e pool, min-energy policy;
* ``uniform_cap``   — same pool under a binding cluster power cap;
* ``hetero``        — mixed 2×v5p + 4×v5e + 2×v5lite pool, risk-aware
  joint (class, clock) placement;
* ``hetero_cap``    — the mixed pool under the cap;
* ``tenant``        — classless pool on a mixed-SLA-tier stream (PR 7):
  tier-priority queue keys and tier-weighted urgencies must not knock
  dispatch off the vectorized fast path, so this scenario rides the
  same ≥3x speedup gate as the untagged streams (admission control is
  deliberately absent — its per-arrival queue scan is an overload
  feature, not a steady-state dispatch cost);
* ``coldstart``     — classless pool on a stream where a third of the
  jobs come from never-profiled apps served by synthesized clock-ladders
  (PR 8): cold-table resolution must ride the same batched prefetch and
  scalar-identity contract as profiled tables;
* ``federation``    — classless pool split across a 2-rack facility
  hierarchy (PR 9): demand-weighted cap rebalancing and hierarchical
  grant escalation happen *around* dispatch (advance/commit), so the
  federated coordinator must preserve the scalar/batched identity
  contract and stay on the vectorized fast path;
* ``models``        — classless pool on a stream mixing the paper suite
  with the repo's own model-derived apps (PR 10): per-(config, phase)
  apps registered through the profiling path must resolve through the
  same batched ladder prefetch and scalar-identity contract as the
  hand-written paper apps.

Every scenario runs the *same* job stream twice — ``batch_decide=False``
(scalar oracle) then ``batch_decide=True`` — asserts the two record
streams are identical (same floats, same RNG draws, same dispatch order),
and reports simulated-jobs/sec for each plus the speedup. Prediction
tables are pre-warmed so neither side pays one-time build costs inside
the timed region.

A ``kernel_threshold`` microbench justifies the measured
``DEFAULT_KERNEL_MIN_ROWS`` batch-routing constant (see
:mod:`repro.core.prediction_service`): per-row predictor cost vs batch
size on the numpy path, and on the Pallas kernel path when a TPU backend
is present (on CPU the kernel only runs in interpret mode, so auto-routing
never engages and the kernel column reads null).

Results persist via the shared writer (``benchmarks/common.py``) as
``BENCH_decide.json`` — the committed perf-trajectory baseline
``scripts/ci.sh`` gates against (scripts/check_perf.py): the smoke section
is compared speedup-to-speedup with a tolerance band, and the baseline's
full-scale uniform speedup must stay ≥ 3×.

Usage::

    PYTHONPATH=src python -m benchmarks.bench_decide            # full, writes baseline
    PYTHONPATH=src python -m benchmarks.bench_decide --smoke --json /tmp/d.json
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.bench_coldstart import novel_apps
from benchmarks.common import csv, fixtures, write_bench_json
from repro.core import (ColdStartSynthesizer, FacilityCoordinator,
                        PredictionService, PowerCapCoordinator, RiskAware,
                        V5E_CLASS, V5E_DVFS, V5LITE_CLASS, V5P_CLASS,
                        heterogeneous_workload, make_device_pool,
                        model_app_suite, multi_tenant_workload,
                        register_model_apps, run_schedule, stream_workload)
from repro.core.features import clock_features
from repro.core.prediction_service import (DEFAULT_KERNEL_MIN_ROWS,
                                           kernel_min_rows_default)

N_DEVICES = 8
POOL_SPEC = ((V5P_CLASS, 2), (V5E_CLASS, 4), (V5LITE_CLASS, 2))
JOBS_FULL = 100_000
JOBS_SMOKE = 2_000
#: Fraction of the pool's aggregate sprint draw the cap scenarios allow —
#: binding (devices cannot all sprint at once) without starving the pool.
CAP_FRAC = 0.6


def _service(f) -> PredictionService:
    return PredictionService(V5E_DVFS, predictor=f["predictor"],
                             app_features=f["features"],
                             testbed=f["testbed"])


def _cap_w(f, pool) -> float:
    """Binding cluster cap: idle floor + CAP_FRAC of the pool's aggregate
    sprint headroom (each device at its class's max-clock truth draw,
    worst app)."""
    tb = f["testbed"]
    floor, sprint = 0.0, 0.0
    classes = pool if pool is not None else [None] * N_DEVICES
    for cls in classes:
        d = tb.dvfs if cls is None else cls.dvfs
        idle = tb.idle_power() if cls is None else cls.idle_power()
        floor += idle
        sprint += max(tb.true_power(a, d.max_clock, dvfs=None if cls is None
                                    else d)
                      for a in f["apps"])
    return floor + CAP_FRAC * (sprint - floor)


def _warm_tables(svc: PredictionService, f, pool) -> None:
    """Build every (app, class) ladder outside the timed region so scalar
    and batched runs race on decisions, not one-time table builds."""
    classes = [None] if pool is None else list({c.name: c for c in pool}
                                               .values())
    for cls in classes:
        for app in f["apps"]:
            svc.table(app.name, cls)


def _scenario(f, svc, name: str, jobs: list, pool, cap_w,
              coord_fn=None) -> dict:
    """One scenario: scalar oracle run, batched run, identity + timing.

    ``coord_fn`` (fresh-coordinator factory) overrides the default bare
    :class:`PowerCapCoordinator` so hierarchy variants reuse the same
    identity + timing harness."""
    results = {}
    times = {}
    for label, bd in (("scalar", False), ("batched", True)):
        kw = {}
        if pool is not None:
            kw["device_classes"] = pool
        if coord_fn is not None:
            kw["power_coordinator"] = coord_fn()
        elif cap_w is not None:
            kw["power_coordinator"] = PowerCapCoordinator(
                cap_w, grant_policy="greedy-edf")
        policy = ("min-energy" if pool is None
                  else RiskAware(V5E_DVFS, margin=0.05))
        t0 = time.perf_counter()
        results[label] = run_schedule(
            jobs, policy, f["testbed"], service=svc,
            n_devices=N_DEVICES, queue_aware=False, virtual_pacing=False,
            batch_decide=bd, **kw)
        times[label] = time.perf_counter() - t0
    identical = results["scalar"].records == results["batched"].records
    n = len(jobs)
    row = {
        "jobs": n,
        "scalar_s": round(times["scalar"], 4),
        "batched_s": round(times["batched"], 4),
        "scalar_jobs_per_s": round(n / times["scalar"], 1),
        "batched_jobs_per_s": round(n / times["batched"], 1),
        "speedup": round(times["scalar"] / times["batched"], 3),
        "identical": identical,
        "energy_j": round(results["batched"].total_energy, 3),
        "misses": results["batched"].misses,
    }
    if cap_w is not None:
        row["cap_w"] = round(cap_w, 1)
    csv(f"decide_{name}", times["batched"],
        f"jobs={n} scalar={row['scalar_jobs_per_s']:,.0f}/s "
        f"batched={row['batched_jobs_per_s']:,.0f}/s "
        f"speedup={row['speedup']:.2f}x identical={identical}")
    assert identical, (
        f"{name}: batched decision core diverged from the scalar oracle")
    return row


def run_scenarios(f, n_jobs: int) -> dict:
    """Every scenario on fresh n_jobs-sized streams."""
    tb, apps = f["testbed"], f["apps"]
    pool = make_device_pool(*POOL_SPEC)
    out = {}

    svc = _service(f)
    _warm_tables(svc, f, None)
    uni = list(stream_workload(apps, tb, n_jobs=n_jobs, seed=1,
                               n_devices=N_DEVICES))
    out["uniform"] = _scenario(f, svc, "uniform", uni, None, None)
    out["uniform_cap"] = _scenario(f, svc, "uniform_cap", uni, None,
                                   _cap_w(f, None))
    # same capped stream through the 2-rack facility hierarchy: cap
    # rebalancing + escalation live outside the dispatch decision, so
    # scalar/batched identity must survive the federation untouched
    fed_cap = _cap_w(f, None)
    out["federation"] = _scenario(
        f, svc, "federation", uni, None, fed_cap,
        coord_fn=lambda: FacilityCoordinator(
            fed_cap, (N_DEVICES // 2, N_DEVICES // 2),
            share_policy="demand-weighted", escalation=True,
            grant_policy="greedy-edf"))
    # mild sustained contention so tier-priority keys actually reorder a
    # live queue, but the stream still drains at dispatch-dominated pace
    ten = list(multi_tenant_workload(apps, tb, n_jobs=n_jobs, seed=1,
                                     n_devices=N_DEVICES, overload=1.5))
    out["tenant"] = _scenario(f, svc, "tenant", ten, None, None)

    # cold-start stream: never-profiled apps resolved through synthesized
    # ladders; pre-registered and pre-warmed like the profiled corpus so
    # both sides race on dispatch decisions, not one-time synthesis
    svc_c = _service(f)
    svc_c.attach_synthesizer(ColdStartSynthesizer())
    novel = novel_apps(list(apps)[-4:], 4)
    _warm_tables(svc_c, f, None)
    for app in novel:
        svc_c.note_app(app)
        svc_c.table(app.name, None)
    cold = list(stream_workload(list(apps) + novel, tb, n_jobs=n_jobs,
                                seed=1, n_devices=N_DEVICES))
    out["coldstart"] = _scenario(f, svc_c, "coldstart", cold, None, None)

    # model-derived stream: the repo's own (config, phase) apps (PR 10)
    # ride the same dispatch fast path as the paper suite — features
    # registered through the profiling path, ladders pre-warmed like the
    # profiled corpus (own service copy so the shared fixture dict stays
    # untouched)
    svc_m = PredictionService(V5E_DVFS, predictor=f["predictor"],
                              app_features=dict(f["features"]),
                              testbed=f["testbed"])
    suite = list(model_app_suite())
    register_model_apps(svc_m, tb)
    _warm_tables(svc_m, f, None)
    for app in suite:
        svc_m.table(app.name, None)
    mod = list(stream_workload(list(apps) + suite, tb, n_jobs=n_jobs,
                               seed=1, n_devices=N_DEVICES))
    out["models"] = _scenario(f, svc_m, "models", mod, None, None)

    svc_h = _service(f)
    _warm_tables(svc_h, f, pool)
    het = list(heterogeneous_workload(apps, tb, pool, n_jobs=n_jobs,
                                      seed=1))
    out["hetero"] = _scenario(f, svc_h, "hetero", het, pool, None)
    out["hetero_cap"] = _scenario(f, svc_h, "hetero_cap", het, pool,
                                  _cap_w(f, pool))
    return out


def kernel_threshold_microbench(f, smoke: bool) -> dict:
    """Per-row predictor cost vs batch size — the measurement behind
    ``DEFAULT_KERNEL_MIN_ROWS``. The numpy GBDT path is roughly flat per
    row while the batch's working set stays cache-resident (up to ~512
    rows on the reference host) and degrades several-fold past that —
    single-ladder builds (64 rows) sit comfortably inside the flat
    regime, while multi-app prefetch batches (apps × clocks ≥ 512) sit
    exactly at the spill point, which is where the one-hot-matmul kernel
    formulation is worth engaging on a real TPU."""
    tb, apps, feats = f["testbed"], f["apps"], f["features"]
    target = f["predictor"].power
    clock_X = [clock_features(c, tb.dvfs) for c in tb.dvfs.clock_list()]
    base = np.stack([np.concatenate([feats[a.name], cx])
                     for a in apps for cx in clock_X])
    X = np.concatenate([base] * max(1, 4096 // len(base) + 1))[:4096]
    sizes = (64, 512) if smoke else (64, 128, 256, 512, 1024, 2048, 4096)
    repeat = 3 if smoke else 7
    numpy_us = {}
    for n in sizes:
        best = min(_time_predict(target, X[:n]) for _ in range(repeat))
        numpy_us[n] = round(best / n * 1e6, 3)
    kernel_us = None
    if jax.default_backend() == "tpu" and target.gbdt is not None:
        from repro.kernels import ops
        kernel_us = {}
        for n in sizes:
            Xe = target.enc.transform(X[:n]) if target.enc else X[:n]
            t0 = time.perf_counter()
            np.asarray(ops.gbdt_predict_model(target.gbdt, Xe))
            kernel_us[n] = round((time.perf_counter() - t0) / n * 1e6, 3)
    row = {
        "numpy_us_per_row": numpy_us,
        "kernel_us_per_row": kernel_us,   # null off-TPU: interpret-mode
                                          # timings would be meaningless
        "default_min_rows": DEFAULT_KERNEL_MIN_ROWS,
        "effective_min_rows": kernel_min_rows_default(),
    }
    flat_best = min(numpy_us.values())
    spill = next((n for n, u in sorted(numpy_us.items())
                  if u > 1.5 * flat_best), None)
    row["numpy_spill_rows"] = spill
    csv("decide_kernel_threshold", 0.0,
        " ".join(f"{n}r={u}us" for n, u in numpy_us.items())
        + f" spill~{spill}r default={DEFAULT_KERNEL_MIN_ROWS}"
        + (" kernel=off-tpu" if kernel_us is None else ""))
    return row


def _time_predict(target, X) -> float:
    t0 = time.perf_counter()
    target.predict(X)
    return time.perf_counter() - t0


def main(smoke: bool = False, json_path: "str | None" = None) -> dict:
    f = fixtures()
    payload: dict = {
        "bench": "decide",
        "config": {"n_devices": N_DEVICES, "jobs_full": JOBS_FULL,
                   "jobs_smoke": JOBS_SMOKE, "cap_frac": CAP_FRAC},
    }
    payload["smoke"] = run_scenarios(f, JOBS_SMOKE)
    if not smoke:
        payload["full"] = run_scenarios(f, JOBS_FULL)
        spd = payload["full"]["uniform"]["speedup"]
        print(f"# claim[decide speedup]: batched {spd:.2f}x >= 3x scalar "
              f"on the {JOBS_FULL}-job uniform stream "
              f"({'OK' if spd >= 3.0 else 'FAIL'})")
        assert spd >= 3.0, (
            f"vectorized decision core below the 3x target: {spd:.2f}x")
    payload["kernel_threshold"] = kernel_threshold_microbench(f, smoke)
    if json_path is not None:
        p = write_bench_json("decide", payload, path=json_path)
        print(f"# wrote {p}")
    elif not smoke:
        p = write_bench_json("decide", payload)
        print(f"# wrote baseline {p}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="2k-job scenarios only (CI gate); does not touch "
                         "the committed baseline unless --json is given")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write results to PATH instead of the canonical "
                         "BENCH_decide.json baseline")
    args = ap.parse_args()
    main(smoke=args.smoke, json_path=args.json)
