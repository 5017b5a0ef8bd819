"""The work count behind ``gbdt_roofline`` and the metric readers."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import harness

METRICS = pathlib.Path(harness.__file__).resolve().parent / "metrics"
PEAKS = json.loads((METRICS.parent / "peaks.json").read_text())["devices"]


def read(name, run):
    return harness.reader(METRICS, name)(run)


def test_work_equals_a_hand_count():
    roof = harness.reader(METRICS, "gbdt_roofline").__globals__
    # 1024 rows of 23 features through 400 trees of depth 4
    ops, nbytes = roof["work"](1024, 23, 400, 4)
    assert ops == 1024 * 400 * 4 == 1_638_400
    assert nbytes == 1024 * 23 * 4 + 400 * 4 * 8 + 1024 * 400 * 4
    assert nbytes == 94_208 + 12_800 + 1_638_400
    peaks = PEAKS["TPU v5 lite"]
    t_ops, t_bytes = roof["least_time"]([(1024, 23, 400, 4)], peaks)
    assert t_ops == pytest.approx(1_638_400 / 197e12)
    assert t_bytes == pytest.approx(1_745_408 / 819e9)
    assert roof["bound"]([(1024, 23, 400, 4)], peaks) == "memory"


def run_with(**kw):
    base = dict(setup_s=12.0, window_s=2.0, placed=500,
                latencies_s=np.linspace(0.001, 0.1, 1000), compiles=0)
    base.update(kw)
    return harness.Run(**base)


def test_roofline_share_and_silence():
    peaks = PEAKS["TPU v5 lite"]
    calls = [(1024, 23, 400, 4)] * 10
    t_least = 10 * 1_745_408 / 819e9
    run = run_with(kernel_calls=calls, kernel_s=4 * t_least, peaks=peaks)
    assert read("gbdt_roofline", run) == pytest.approx(25.0)
    assert read("gbdt_roofline", run_with(peaks=peaks)) is None


def test_end_to_end_readers():
    run = run_with()
    assert read("jobs_per_s", run) == 250.0
    assert read("jobs_per_s", run_with(shed=300)) == 250.0
    assert read("setup_s", run) == 12.0
    lat = run.latencies_s * 1e3
    assert read("place_p50_ms", run) == pytest.approx(np.percentile(lat, 50))
    assert read("place_p99_ms", run) == pytest.approx(np.percentile(lat, 99))
    assert read("place_p50_ms", run_with(latencies_s=np.array([]))) is None


def test_shares_from_spans():
    from chipbench.spans import Spans

    sp = Spans()
    sp.by_layer = {"engine": [(0.0, 2.0)], "gen": [(0.1, 0.3)],
                   "predict": [(0.5, 1.0)], "decide": [(1.0, 1.2)],
                   "coord": [(0.9, 1.1), (1.5, 1.6)]}
    run = run_with(spans=sp, traced_window_s=4.0, busy_s=1.0)
    assert read("gen_share", run) == pytest.approx(10.0)
    assert read("predict_share", run) == pytest.approx(25.0)
    assert read("coord_share", run) == pytest.approx(15.0)
    assert read("decide_share", run) == pytest.approx(10.0)
    # children cover [0.1,0.3] + [0.5,1.2] + [1.5,1.6] = 1.0 of 2.0
    assert read("engine_self_share", run) == pytest.approx(50.0)
    assert read("device_idle", run) == pytest.approx(75.0)
