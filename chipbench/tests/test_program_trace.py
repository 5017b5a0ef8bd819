"""The in-program metrics and trace keys of ``chipbench/program_trace.py``
on constructed runs, on the constructed trace of ``test_trace``, and in a
traced run of the harness on the CPU."""
import json

import numpy as np
import pytest

from chipbench import harness, program_trace
from chipbench.tests.test_runs import root  # noqa: F401 (fixture)
from chipbench.tests.test_trace import constructed

MS = 1_000_000
BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
#: the metrics ``BENCHMARK.json`` reads from the program's recorder
RECORDER = ("wave_stack_share", "kernel_launch_share", "kernel_wait_share",
            "leaf_sum_share", "place_wait_predict_share",
            "rack_advance_share", "rebalance_share")
PROGRAM_METRICS = [m for m in BENCH["per_layer"] if m["name"] in RECORDER]
#: host clock of the window's start; the trace's window starts at 30 ms
T_WINDOW = 1000.0


def ns(host_s: float) -> int:
    return round(host_s * 1e9)


def records():
    """Recorder spans on the host clock: a job waiting behind a wave whose
    launch covers the trace's longest idle gap (50-95 ms)."""
    w = T_WINDOW - 0.030            # host time of the trace's 0 ms
    return [
        ("engine.job", ns(w + 0.045), ns(w + 0.100), None, 7),
        ("predict.wave", ns(w + 0.050), ns(w + 0.095), "engine.wave", 1),
        ("predict.launch", ns(w + 0.051), ns(w + 0.094), "predict.wave", 1),
        ("coord.rebalance", ns(w + 0.096), ns(w + 0.099), "coord.advance",
         7),
        ("predict.wave", ns(w + 0.001), ns(w + 0.002), None, 0),
    ]


def annotated_trace():
    """``test_trace``'s trace with the profiler's annotations of the wave
    and its launch, 3 us and 1 us after the recorder's starts."""
    planes = constructed()
    planes[0]["lines"]["python"] += [
        ("predict.wave:1", 50 * MS + 3_000, 45 * MS),
        ("predict.launch:1", 51 * MS + 1_000, 43 * MS)]
    return planes


def run_of(window_s=0.070, **kw):
    run = harness.Run(setup_s=1.0, window_s=window_s, placed=10,
                      latencies_s=np.zeros(0), compiles=0, **kw)
    run.program_spans = program_trace.ProgramSpans(
        records(), (T_WINDOW, T_WINDOW + window_s))
    return run


def read(name, run):
    return harness.reader(harness.HERE / "metrics", name)(run)


def test_spans_in_host_seconds_with_keys_and_depth():
    sp = run_of().program_spans
    assert sp.by_layer["predict.wave"][0] == pytest.approx(
        (T_WINDOW - 0.029, T_WINDOW - 0.028))
    assert sp.keys["predict.wave"] == [0, 1]
    assert sp.depth("predict.launch") == 2     # wave, then engine.wave
    assert sp.depth("engine.job") == 0
    # the set-up wave lies before the window
    assert sp.in_window("predict.wave") == [sp.by_layer["predict.wave"][1]]


@pytest.mark.parametrize("name,span", [
    ("kernel_launch_share", (0.051, 0.094)),
    ("rebalance_share", (0.096, 0.099)),
])
def test_window_shares(name, span):
    assert read(name, run_of()) == pytest.approx(
        100 * (span[1] - span[0]) / 0.070)


@pytest.mark.parametrize("name", [m["name"] for m in PROGRAM_METRICS])
def test_readers_read_nothing_without_the_recorder(name):
    run = harness.Run(setup_s=1.0, window_s=1.0, placed=1,
                      latencies_s=np.zeros(0), compiles=0)
    assert read(name, run) is None


def test_absent_span_reads_nothing():
    assert read("kernel_wait_share", run_of()) is None


def test_place_wait_predict_share():
    # the job waits 55 ms, 45 of them behind the wave
    assert read("place_wait_predict_share", run_of()) == pytest.approx(
        100 * 45 / 55)


def test_kernel_useful_share():
    calls = [(1088, 23, 400, 4), (1024, 23, 400, 4)]
    run = run_of(kernel_calls=calls)
    want = 100 * (1088 + 1024) * 400 / ((1280 + 1024) * 512)
    assert read("kernel_useful_share", run) == pytest.approx(want)
    assert read("kernel_useful_share", run_of()) is None


def test_idle_spans_and_clock_skew():
    planes = annotated_trace()
    run = run_of()
    coarse = [["engine", 0.045], ["setup", 0.022], ["setup", 0.010]]
    breakdown = {"idle_gaps": [list(g) for g in coarse]}
    program_trace.extend(breakdown, run, planes, records(), T_WINDOW)
    assert breakdown["idle_gaps"] == coarse
    # the launch, two spans deep, covers the longest gap; nothing covers
    # the others
    assert breakdown["idle_spans"] == [["predict.launch", 0.045],
                                       ["setup", 0.022], ["setup", 0.010]]
    assert breakdown["clock_skew_us"] == pytest.approx(3.0, abs=1e-3)


def test_clock_skew_without_annotations_is_none():
    sp = run_of().program_spans
    assert program_trace.clock_skew_us(constructed(), sp, 0.0) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory, root):
    """One traced ``run_cell`` of ``tiny-novel`` on the CPU, reporting the
    per-layer metrics of fed64-novel: ``(result, run)``. The profiler's CPU
    trace gains a device plane with one operation, and ``peaks.json`` an
    entry for the CPU, so that the trace's reduction runs."""
    import json
    import time

    import repro.core.prediction_service as ps
    from chipbench.tests.test_runs import SEED, keep_runs

    here = tmp_path_factory.mktemp("peaks")
    peaks = json.loads((harness.HERE / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (here / "peaks.json").write_text(json.dumps(peaks))
    read_xplane = harness.trace_mod.read_xplane

    def with_device(logdir):
        planes = read_xplane(logdir)
        lo, _ = harness.trace_mod.find_event(planes, "chipbench_traced")
        planes.append({"name": "/device:TPU:0", "lines": {
            harness.trace_mod.OPS_LINE: [("fusion.1", lo + 1000, 1000)]}})
        return planes

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ps, "_on_tpu", lambda: True)
        mp.setenv("REPRO_GBDT_KERNEL_MIN_ROWS", "48")
        mp.setattr(harness, "HERE", here)
        mp.setattr(harness.trace_mod, "read_xplane", with_device)
        runs = keep_runs(mp)
        bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
        cell = harness.load_cell("tiny-novel", root=root)
        cell["per_layer"] = [m for m in bench["per_layer"]
                             if "fed64-novel" in m["workloads"]]
        out = harness.run_cell(cell, SEED, 1.0, True, time.perf_counter(),
                               check_chips=False)
    return out, runs[0]


def test_traced_run_records_from_the_prefetch_to_the_window_end(traced):
    from repro.core import tracing

    out, run = traced
    assert out["correct"], out["checks"]
    assert not tracing.ON
    sp = run.program_spans
    t0, t1 = sp.window
    waves = sp.by_layer["predict.wave"]
    # the suite prefetch of set-up, then waves of new apps in the window
    assert waves[0][1] <= t0 and sp.in_window("predict.wave")
    assert max(e for ivs in sp.by_layer.values() for _, e in ivs) <= t1
    assert set(out["breakdown"]) >= {"device_ops", "idle_gaps",
                                     "idle_spans", "clock_skew_us"}


def test_traced_run_reports_its_cells_program_metrics(traced):
    out, _ = traced
    mine = {m["name"] for m in PROGRAM_METRICS
            if "fed64-novel" in m["workloads"]}
    assert len(mine) == 7
    assert all(out["metrics"][name]["value"] > 0 for name in mine), (
        out["metrics"])


def test_traced_run_reports_what_its_spans_cost(traced):
    out, run = traced
    cost = out["breakdown"]["span_cost"]
    assert cost["recorder_spans"] > 0 and cost["wrapper_calls"] > 0
    assert 0 < cost["recorder_span_us"] < 1e3
    assert 0 < cost["wrapper_call_us"] < 1e3
    assert cost["recorder_share"] == pytest.approx(
        cost["recorder_spans"] * cost["recorder_span_us"] * 1e-4
        / run.window_s)


def test_span_cost_counts_the_spans_inside_the_window():
    from chipbench.spans import Spans
    from repro.core import tracing

    run = run_of()
    run.spans = Spans()
    t0 = T_WINDOW
    run.spans.by_layer = {
        "engine": [(t0, t0 + 0.070)],
        "coord": [(t0 + 0.001, t0 + 0.002), (t0 - 0.010, t0 - 0.009)],
        "gen": [(t0 + 0.003, t0 + 0.004), (t0 + 0.060, t0 + 0.080)]}
    cost = program_trace.span_cost(run, n=100)
    # the job, the window's wave, its launch and the rebalance lie in the
    # window, set-up's wave does not; one wrapped call of each layer ends
    # inside it
    assert cost["recorder_spans"] == 4
    assert cost["wrapper_calls"] == 2
    assert not tracing.ON and tracing.take() == []


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_benchmark_metric_has_a_reader(kind):
    for m in BENCH[kind]:
        assert callable(harness.reader(harness.HERE / "metrics",
                                       m["name"])), m["name"]


def test_kernel_useful_share_equals_the_service_counters(monkeypatch):
    import repro.core.prediction_service as ps
    from chipbench import fixtures
    from chipbench.tests.test_runs import TINY
    from repro.core import V5E_DVFS, PredictionService

    monkeypatch.setattr(ps, "_on_tpu", lambda: True)
    f = fixtures.build(TINY, 11)
    svc = PredictionService(V5E_DVFS, predictor=f["predictor"],
                            app_features=dict(f["features"]),
                            kernel_min_rows=1)
    calls = []
    with harness._kernel_calls(calls):
        svc.prefetch_tables([a.name for a in f["suite"][:5]])
    run = run_of(kernel_calls=calls)
    assert len(calls) == 2
    want = 100 * svc.stats.kernel_cells / svc.stats.kernel_padded_cells
    assert want < 100
    assert read("kernel_useful_share", run) == pytest.approx(want, rel=0,
                                                             abs=1e-12)
