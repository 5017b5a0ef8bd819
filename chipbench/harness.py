"""One run of one benchmark cell: set-up, the measured window, the
comparison that decides ``correct``, and the result object.

A cell is found by name in ``BENCHMARK.json``; its configuration file, its
traffic file (``chipbench/traffic/<traffic>.json``) and its metric readers
(``chipbench/metrics/<metric>.py``) are found by the names there. Nothing
here names a cell, a configuration, a mix or a metric.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from . import fixtures, gen, program_trace, reference
from . import spans as spans_mod
from . import trace as trace_mod

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent

#: JAX monitoring event of a program lowered for the first time in this
#: process; it comes before every compilation and every load from the
#: persistent compilation cache.
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def is_kernel(short: str) -> bool:
    """The Pallas GBDT kernel's operation in the trace: a custom call named
    after its jitted entry, ``gbdt_leaf_indices``."""
    return short.startswith("%gbdt_leaf_indices") and "custom-call" in short


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration,
    traffic parameters and the metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = json.loads((root / files[cell["config"]]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
        .read_text())

    def mine(m):
        return name in m.get("workloads", (name,))

    return {"name": name, "chips": int(cell["chips"]), "config": config,
            "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "metrics_dir": root / "chipbench" / "metrics"}


def reader(metrics_dir: pathlib.Path, name: str) -> Callable:
    """The ``read(run)`` function of ``metrics_dir/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", metrics_dir / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read it. Times are host
    clock seconds unless named otherwise."""

    setup_s: float
    window_s: float
    placed: int                         # jobs dispatched (a shed one is not)
    latencies_s: np.ndarray             # wait -> dispatch, per dispatched job
    compiles: int                       # programs lowered in the window
    spans: Optional[spans_mod.Spans] = None
    #: (rows, features, trees, depth) of each kernel call in the window
    kernel_calls: list = dataclasses.field(default_factory=list)
    kernel_s: float = 0.0               # device seconds of those calls
    busy_s: float = 0.0                 # device busy in the traced window
    traced_window_s: float = 0.0
    peaks: Optional[dict] = None        # peaks.json entry of this chip
    shed: int = 0                       # jobs admission control shed
    #: job id -> host clock at which its wait began: its first admission
    #: check, or its enqueue where no admission control runs
    started: dict = dataclasses.field(default_factory=dict)
    #: the window's own program objects, for readers of their counters:
    #: ``service``, ``coordinator``, ``admission`` (or None), ``result``
    parts: dict = dataclasses.field(default_factory=dict)
    #: the program's recorder spans (traced runs)
    program_spans: Optional[program_trace.ProgramSpans] = None


def require_chips(n: int) -> list:
    """The devices of this process, or :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform!r} device(s)")
    return devs


def coordinator(config: dict):
    """A fresh facility coordinator at the configuration's fixed cap."""
    from repro.core import FacilityCoordinator

    c = config["coordinator"]
    return FacilityCoordinator(float(config["cap_w"]), config["racks"],
                               share_policy=c["share_policy"],
                               grant_policy=c["grant_policy"],
                               guard=float(c["guard"]))


def admission(config: dict):
    """A fresh admission controller with the configuration's ``admission``
    keyword arguments; None where the configuration has none."""
    from repro.core import AdmissionController

    kw = config.get("admission")
    return None if kw is None else AdmissionController(**kw)


def testbed(config: dict, seed: int):
    """The fleet the schedule runs on, with the configuration's
    measurement noise."""
    from repro.core import Testbed

    return Testbed(noise=float(config["measurement_noise"]), seed=seed)


def policy(config: dict):
    from repro.core import V5E_DVFS, RiskAware

    return RiskAware(V5E_DVFS, margin=float(config["policy"]["margin"]))


def wave_rows(pool, traffic: dict, min_rows: int) -> list[int]:
    """Every row count a prefetch wave of new apps can send to the kernel:
    apps x ladder length, from the routing threshold up to two bursts of
    new apps in one wave."""
    if float(traffic.get("novel_share", 0.0)) <= 0.0:
        return []
    burst = max(1, int(len(pool) * float(traffic["burst_frac"])))
    ladders = {len(c.dvfs.clock_list()) for c in pool}
    return sorted({k * L for L in ladders for k in range(1, 2 * burst + 1)
                   if k * L >= min_rows})


def warm_kernel_shapes(predictor, rows: list[int]) -> None:
    """Run the kernel once at each row count, with the float64 features
    the service passes, so that nothing compiles in the window."""
    from repro.kernels import ops

    shapes = {(t.gbdt.feats.shape, t.gbdt.split_gain.shape[0]): t.gbdt
              for t in (predictor.power, predictor.time)}
    for (_, n_feat), g in shapes.items():
        for n in rows:
            X = np.zeros((n, n_feat))
            np.asarray(ops.gbdt_leaf_indices(X, g.feats, g.thresholds))


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@contextlib.contextmanager
def _engine_span(sp: spans_mod.Spans):
    """Time ``EventEngine.run`` while the block runs."""
    from repro.core import EventEngine

    orig = EventEngine.__dict__["run"]
    EventEngine.run = sp.wrap(orig, "engine", "engine.run")
    try:
        yield
    finally:
        EventEngine.run = orig


@contextlib.contextmanager
def _kernel_calls(calls: list):
    """Record the shapes of every kernel call while the block runs."""
    from repro.kernels import ops

    orig = ops.gbdt_leaf_indices

    def recorded(X, feats, thresholds, **kw):
        n, n_feat = np.shape(X)
        n_trees, depth = np.shape(feats)
        calls.append((n, n_feat, n_trees, depth))
        return orig(X, feats, thresholds, **kw)

    ops.gbdt_leaf_indices = recorded
    try:
        yield
    finally:
        ops.gbdt_leaf_indices = orig


def _wait_from_check(check: Callable, started: dict,
                     now: Callable) -> Callable:
    """Admission control's ``check`` that starts each job's wait at its
    first check, so that a job parked and released later waits from its
    arrival at the controller, not from its release."""
    def timed(job, t, queue):
        started.setdefault(job.job_id, now())
        return check(job, t, queue)
    return timed


def _reduce_trace(run: Run, logdir: str, t_window: float,
                  records: list) -> dict:
    """Busy time, kernel time and the breakdown from the profiler trace;
    the recorder's ``records`` go to ``run.program_spans`` and name the
    idle gaps once more (:func:`program_trace.extend`); ``span_cost`` says
    what both span systems took from the window."""
    planes = trace_mod.read_xplane(logdir)
    tr_lo, tr_hi = trace_mod.find_event(planes, "chipbench_traced")
    w_lo, w_hi = trace_mod.find_event(planes, "chipbench_window")
    run.busy_s = trace_mod.busy_s(planes, tr_lo, tr_hi)
    run.traced_window_s = (tr_hi - tr_lo) * 1e-9
    run.kernel_s = sum(trace_mod.op_seconds(planes, w_lo, w_hi,
                                            is_kernel).values())
    ops = trace_mod.op_seconds(planes, tr_lo, tr_hi)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    offset = w_lo * 1e-9 - t_window      # trace clock minus host clock
    gaps = []
    for g_lo, g_hi in trace_mod.idle_gaps(planes, tr_lo, tr_hi)[:10]:
        lo, hi = g_lo * 1e-9 - offset, g_hi * 1e-9 - offset
        gaps.append([_host_layer(run.spans, lo, hi, t_window),
                     (g_hi - g_lo) * 1e-9])
    out = {"device_ops": [[n, s] for n, s in top], "idle_gaps": gaps}
    program_trace.extend(out, run, planes, records, t_window)
    out["span_cost"] = program_trace.span_cost(run)
    return out


def _host_layer(sp: spans_mod.Spans, lo: float, hi: float,
                t_window: float) -> str:
    """What the host did for most of ``[lo, hi]``: set-up before the
    window, else the layer whose spans cover most of it (the engine's own
    code where none does)."""
    if hi <= t_window:
        return "setup"
    cover = {layer: spans_mod.union_length(sp.by_layer.get(layer, ()),
                                           lo, hi)
             for layer in ("gen", "predict", "decide", "coord")}
    layer, most = max(cover.items(), key=lambda kv: kv[1])
    rest = (hi - max(lo, t_window)) - sum(cover.values())
    return layer if most >= rest else "engine"


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, check_chips: bool = True,
             fault: Optional[Callable] = None) -> dict:
    """One run; returns the result object that the command prints.

    ``t_start`` is the host clock at process start. ``check_chips=False``
    and ``fault`` serve the benchmark's own tests: ``fault(service,
    coordinator, admission)`` is called before the window to break the
    timed path. A traced run has the program's span recorder on from the
    suite prefetch to the end of the window."""
    import jax

    from repro.core import (V5E_DVFS, EngineHooks, PredictionService,
                            profile_features, run_schedule, tracing)

    devs = require_chips(cell["chips"]) if check_chips else jax.devices()
    config, traffic = cell["config"], cell["traffic"]
    # the stream is the mix's own, the same for every run seed; the run
    # seed draws the predictor, the profiles and the measurement noise
    s_stream = int(traffic["stream_seed"])
    s_novel, s_tier = (fixtures.sub_seed(s_stream, k) for k in (2, 5))
    s_sched, s_prof = (fixtures.sub_seed(seed, k) for k in (3, 4))
    now = time.perf_counter

    f = fixtures.build(config, seed)
    pool = fixtures.pool_of(config)
    classes = list({c.name: c for c in pool}.values())
    tb, predictor = f["testbed"], f["predictor"]
    features = dict(f["features"])
    svc = PredictionService(V5E_DVFS, predictor=predictor,
                            app_features=dict(features), testbed=tb)
    warm_kernel_shapes(predictor,
                       wave_rows(pool, traffic, svc.kernel_min_rows))
    if trace:
        tracing.take()
        tracing.enable()

    sp = spans_mod.Spans() if trace else None
    logdir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    if trace:
        jax.profiler.start_trace(logdir, profiler_options=_profile_options())
    traced = jax.profiler.TraceAnnotation("chipbench_traced")
    traced.__enter__()
    for cls in classes:                 # every suite table, one wave each
        svc.prefetch_tables([a.name for a in f["suite"]], (cls,))

    coord, pol, adm = coordinator(config), policy(config), admission(config)
    if fault is not None:
        fault(svc, coord, adm)
    started: dict[int, float] = {}
    latencies: list[float] = []
    if adm is not None:
        adm.check = _wait_from_check(adm.check, started, now)

    def on_admit(job, _t):
        started.setdefault(job.job_id, now())

    def on_dispatch(job, _dev, _clock, _t):
        latencies.append(now() - started[job.job_id])

    def on_novel(app):
        # one default-clock profiling run, registered in the service: the
        # documented path of a new app (as register_model_apps does)
        vec = profile_features(app, tb, rng=np.random.default_rng(
            [s_prof, app.seed]))
        features[app.name] = vec
        svc.app_features[app.name] = vec

    deadline = [math.inf]
    jobs: list = []
    src = gen.stream(f["suite"], tb, pool, traffic, seed=s_stream,
                     novel_seed=s_novel, tier_seed=s_tier,
                     on_novel=on_novel,
                     stop=lambda: now() >= deadline[0])
    if trace:
        src = sp.iterate(src, "gen")
        sp.wrap_methods(svc, ("prefetch_tables", "base_table", "table"),
                        "predict")
        sp.wrap_methods(pol, ("select_device_clock", "select_capped"),
                        "decide")
        sp.wrap_methods(coord, ("advance", "offer", "escalate", "commit",
                                "truncate", "next_release", "potential_w"),
                        "coord")

    def recorded(it):
        for job in it:
            jobs.append(job)
            yield job

    lowered = [0, False]

    def on_event(event, _secs, **_kw):
        if lowered[1] and event == LOWER_EVENT:
            lowered[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    calls: list = []
    with contextlib.ExitStack() as stack:
        if trace:
            stack.enter_context(_engine_span(sp))
            stack.enter_context(_kernel_calls(calls))
        stack.enter_context(jax.profiler.TraceAnnotation("chipbench_window"))
        t0 = now()
        deadline[0] = t0 + seconds
        lowered[1] = True
        result = run_schedule(
            recorded(src), pol, testbed(config, s_sched), seed=s_sched,
            service=svc, device_classes=pool, power_coordinator=coord,
            hooks=EngineHooks(on_admit=on_admit, on_dispatch=on_dispatch),
            admission=adm)
        t1 = now()
        lowered[1] = False
    jax.monitoring.unregister_event_duration_listener(on_event)
    traced.__exit__(None, None, None)
    if trace:
        records = tracing.take()
        tracing.disable()
        jax.profiler.stop_trace()

    stats = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    run = Run(setup_s=t0 - t_start, window_s=t1 - t0,
              placed=len(result.records), shed=result.shed_count,
              latencies_s=np.asarray(latencies), compiles=lowered[0],
              spans=sp, started=started,
              parts={"service": svc, "coordinator": coord, "admission": adm,
                     "result": result})
    breakdown = None
    if trace:
        peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
        if devs[0].device_kind not in peaks:
            raise KeyError(f"no peaks for device kind "
                           f"{devs[0].device_kind!r} in peaks.json")
        run.peaks = peaks[devs[0].device_kind]
        run.kernel_calls = calls
        breakdown = _reduce_trace(run, logdir, t0, records)
        shutil.rmtree(logdir, ignore_errors=True)
        device["busy_s"] = run.busy_s
        device["window_s"] = run.traced_window_s

    checks = compare(config, traffic, svc, f, features, pool, classes, jobs,
                     result, s_sched)
    metrics = {}
    for m in cell["per_layer" if trace else "end_to_end"]:
        value = reader(cell["metrics_dir"], m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": passed(checks), "attempted": len(jobs),
           "failed": len(jobs) - len(result.records),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def compare(config: dict, traffic: dict, svc, f: dict, features: dict,
            pool, classes, jobs: list, result, s_sched: int) -> dict:
    """The numbers that decide ``correct``, each with its limit."""
    from repro.core import V5E_DVFS, PredictionService, run_schedule

    kernel_batches = svc.stats.kernel_batches
    differ, missing, compared = reference.tables_differ(
        svc, f["predictor"], features, list(features), classes)
    checks = {"tables_differ": {"value": differ, "limit": 0},
              "tables_missing": {"value": missing, "limit": 0}}
    sheddable = {t["name"] for t in traffic.get("tiers", ())
                 if t["sheddable"]}
    held = reference.guarantees(result, jobs, pool, float(config["cap_w"]),
                                s_sched, float(config["measurement_noise"]),
                                sheddable)
    for name, value in held.items():
        checks[name] = {"value": value,
                        "limit": reference.CAP_SLACK_W
                        if name == "cap_excess_w" else 0}
    ref = PredictionService(V5E_DVFS, predictor=f["predictor"],
                            app_features=dict(features), testbed=f["testbed"],
                            use_kernel=False)
    want = run_schedule(jobs, policy(config), testbed(config, s_sched),
                        seed=s_sched, service=ref, device_classes=pool,
                        power_coordinator=coordinator(config),
                        batch_decide=False, admission=admission(config))
    for name, gap in reference.schedule_gaps(result, want).items():
        checks[name] = {"value": gap, "limit": 0}
    checks["tables_compared"] = {"value": compared, "floor": 1}
    checks["kernel_batches"] = {"value": kernel_batches, "floor": 1}
    return checks


def passed(checks: dict) -> bool:
    """Every number within its limit (at most ``limit``, at least
    ``floor``)."""
    return all(c["value"] <= c["limit"] if "limit" in c
               else c["value"] >= c["floor"] for c in checks.values())
