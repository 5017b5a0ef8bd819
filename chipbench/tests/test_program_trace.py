"""The in-program metrics and trace keys of ``chipbench/program_trace.py``
on constructed runs and on the constructed trace of ``test_trace``."""
import types

import numpy as np
import pytest

from chipbench import harness, program_trace
from chipbench.tests.test_trace import constructed

MS = 1_000_000
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


@pytest.mark.parametrize("name", [m["name"] for m in program_trace.METRICS])
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


def test_recorder_on_wraps_and_restores(monkeypatch):
    from repro.core import tracing

    calls = []
    fake = types.SimpleNamespace(
        warm_kernel_shapes=lambda *a: calls.append("warm"),
        _reduce_trace=lambda run, logdir, t: {
            "idle_gaps": [["engine", 0.045]]})
    warm, reduce = fake.warm_kernel_shapes, fake._reduce_trace
    monkeypatch.setattr(program_trace.trace_mod, "read_xplane",
                        lambda logdir: annotated_trace())
    run = run_of()
    with program_trace.recorder_on(fake):
        fake.warm_kernel_shapes()
        assert tracing.ON
        tracing.end(tracing.begin("coord.advance"))
        out = fake._reduce_trace(run, "logdir", T_WINDOW)
        assert not tracing.ON
    assert calls == ["warm"]
    assert (fake.warm_kernel_shapes, fake._reduce_trace) == (warm, reduce)
    assert list(run.program_spans.by_layer) == ["coord.advance"]
    assert set(out) == {"idle_gaps", "idle_spans", "clock_skew_us"}


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
