"""The program's span recorder (repro.core.tracing): off it records
nothing and reads no clock; on it changes no record of a schedule; its
spans nest as documented; the kernel's cell counters match its padding."""
import dataclasses

import numpy as np
import pytest

from repro.configs.paper_suite import PAPER_APPS
from repro.core import (EnergyTimePredictor, FacilityCoordinator,
                        PredictionService, PredictorConfig, Testbed,
                        V5E_DVFS, build_dataset, multi_rack_workload,
                        profile_features, run_schedule, tracing)
from repro.core.gbdt import GBDTParams
from repro.kernels import gbdt_predict, ops

APPS = list(PAPER_APPS)[:6]
SMALL = PredictorConfig(
    gbdt=GBDTParams(iterations=60, depth=3, learning_rate=0.15),
    gbdt_time=GBDTParams(iterations=60, depth=3, learning_rate=0.15))
RACKS = [4, 4]
#: one new app's ladder (64 v5e clocks) stays on numpy, two reach the kernel
MIN_ROWS = 65
#: tight enough that the facility moves watts between racks
CAP_W = 700.0

SPANS = {"engine.wave", "engine.arrival", "engine.job", "engine.decide",
         "predict.wave", "predict.stack", "predict.launch",
         "predict.device_wait", "predict.leaf_sum", "predict.numpy",
         "predict.store", "coord.advance", "coord.rack_advance",
         "coord.rebalance", "coord.facility_escalate"}
WAVE_CHILDREN = {"predict.stack", "predict.launch", "predict.device_wait",
                 "predict.leaf_sum", "predict.numpy", "predict.store"}


@pytest.fixture(scope="module")
def setting():
    """A fitted predictor, profiled suite apps and a bursty stream in
    which every third job is the first run of a new variant of its app,
    profiled before the run, so that its table is built in the run: one
    or two new apps a burst of four."""
    tb = Testbed(seed=0)
    X, yp, yt, _ = build_dataset(APPS, tb, seed=0)
    predictor = EnergyTimePredictor(SMALL).fit(X, yp, yt)
    rng = np.random.default_rng(7)
    feats = {a.name: profile_features(a, tb, rng=rng) for a in APPS}
    jobs = []
    for i, job in enumerate(multi_rack_workload(
            APPS, tb, n_devices=sum(RACKS), n_jobs=64, seed=3)):
        if i % 3 == 1:
            app = dataclasses.replace(job.app, name=f"{job.app.name}-v{i}")
            feats[app.name] = profile_features(app, tb, rng=rng)
            job = dataclasses.replace(job, app=app)
        jobs.append(job)
    return predictor, feats, jobs


@pytest.fixture()
def recorder():
    tracing.take()
    yield tracing
    tracing.disable()
    tracing.take()


def schedule(setting, on: bool):
    predictor, feats, jobs = setting
    svc = PredictionService(V5E_DVFS, predictor=predictor,
                            app_features=dict(feats),
                            kernel_min_rows=MIN_ROWS)
    coord = FacilityCoordinator(CAP_W, RACKS)
    if on:
        tracing.enable()
    try:
        result = run_schedule(jobs, "risk-aware", Testbed(seed=1000),
                              service=svc, n_devices=sum(RACKS),
                              power_coordinator=coord)
    finally:
        tracing.disable()
    return result, svc, coord


@pytest.fixture()
def kernel_on_cpu(monkeypatch):
    """Route batches of at least MIN_ROWS rows to the kernel (interpreted
    on the CPU)."""
    import repro.core.prediction_service as ps

    monkeypatch.setattr(ps, "_on_tpu", lambda: True)


def test_off_records_nothing_and_reads_no_clock(setting, recorder,
                                                monkeypatch):
    reads = []
    monkeypatch.setattr(tracing, "clock", lambda: reads.append(1) or 0)
    result, _, _ = schedule(setting, on=False)
    assert tracing.take() == []
    assert reads == []
    assert len(result.records) == len(setting[2])


def test_on_changes_no_record(setting, recorder, kernel_on_cpu):
    off, svc_off, _ = schedule(setting, on=False)
    on, svc_on, coord = schedule(setting, on=True)
    assert tracing.take()
    assert coord.stats.escalations > 0 and coord.stats.rebalances > 0
    assert svc_on.stats.kernel_batches == svc_off.stats.kernel_batches > 0
    assert len(on.records) == len(off.records)
    for a, b in zip(on.records, off.records):
        assert dataclasses.astuple(a) == dataclasses.astuple(b)


@pytest.fixture(scope="module")
def traced(setting):
    """The spans of one schedule with the recorder on, as
    ``[(name, start, end, parent, key)]``, and its result."""
    import repro.core.prediction_service as ps

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ps, "_on_tpu", lambda: True)
        tracing.take()
        result, _, _ = schedule(setting, on=True)
        spans = tracing.take()
    return spans, result


def test_every_span_appears(traced):
    spans, _ = traced
    assert {s[0] for s in spans} == SPANS
    assert all(s[1] <= s[2] for s in spans)


def test_children_lie_inside_their_parents(traced):
    spans, _ = traced
    for name, lo, hi, parent, key in spans:
        if parent is None:
            continue
        outer = [p for p in spans
                 if p[0] == parent and p[1] <= lo and hi <= p[2]]
        assert outer, (name, parent, key)
        if name != "predict.wave":      # a wave numbers itself
            assert any(p[4] == key for p in outer), (name, parent, key)


def test_wave_children_cover_each_wave(traced):
    spans, _ = traced
    waves = [s for s in spans if s[0] == "predict.wave"]
    assert waves
    for _, lo, hi, _, key in waves:
        inside = sum(e - s for n, s, e, _, k in spans
                     if n in WAVE_CHILDREN and k == key and lo <= s
                     and e <= hi)
        assert inside >= 0.9 * (hi - lo), key
    # waves number themselves 1, 2, ... in the order they began
    assert sorted(w[4] for w in waves) == list(range(1, len(waves) + 1))


def test_every_dispatched_job_has_one_wait(traced):
    spans, result = traced
    waits = [s[4] for s in spans if s[0] == "engine.job"]
    assert sorted(waits) == sorted(r.job_id for r in result.records)
    decided = {s[4] for s in spans if s[0] == "engine.decide"}
    assert decided == set(waits)


def test_kernel_cells_match_the_padding(setting, kernel_on_cpu,
                                        monkeypatch):
    predictor, feats, _ = setting
    seen = []
    orig = gbdt_predict.gbdt_leaf_indices

    def shapes(Xp, onehot, thrp, **kw):
        seen.append((Xp.shape[0], thrp.shape[1]))
        return orig(Xp, onehot, thrp, **kw)

    monkeypatch.setattr(gbdt_predict, "gbdt_leaf_indices", shapes)
    svc = PredictionService(V5E_DVFS, predictor=predictor,
                            app_features=dict(feats), kernel_min_rows=1)
    svc.prefetch_tables([a.name for a in APPS[:5]])   # 5 x 64 rows
    n, n_trees = 5 * 64, predictor.power.gbdt.feats.shape[0]
    n_pad, t_pad = ops.gbdt_padded_shape(n, n_trees)
    assert (n_pad, t_pad) == (512, n_trees)
    assert seen == [(n_pad, t_pad)] * 2                # power, then time
    assert svc.stats.kernel_cells == 2 * n * n_trees
    assert svc.stats.kernel_padded_cells == 2 * n_pad * t_pad
    assert svc.stats.kernel_cells <= svc.stats.kernel_padded_cells


def test_padded_shape_of_the_predictor_size():
    """400 trees reach the kernel as 512 lanes; 1,088 rows as 1,280."""
    assert ops.gbdt_padded_shape(1088, 400) == (1280, 512)
    assert ops.gbdt_padded_shape(1024, 400) == (1024, 512)
    assert ops.gbdt_padded_shape(64, 400) == (64, 512)
