"""Whole runs of a cell on the CPU at a small size: a configuration added
as a file runs without any other edit, so does a tiered deployment under
admission control, the comparison passes on the program as it is, and it
fails on the control and on each fault.

The look for a chip is skipped, the kernel runs in interpret mode, and the
routing threshold is lowered so that small waves reach the kernel.
"""
import json
import shutil
import time

import pytest

from chipbench import control, fixtures, gen, harness
from chipbench.tests.test_gen import TIERS, fields

TINY = {
    "name": "tiny", "source": "a small fleet for tests", "reduced": [],
    "pool": [["v5p", 2], ["v5e", 4], ["v5lite", 2]], "racks": [2, 4, 2],
    "coordinator": {"share_policy": "demand-weighted",
                    "grant_policy": "slack-weighted", "guard": 0.2},
    "policy": {"name": "risk-aware", "margin": 0.05}, "model_apps": False,
    "predictor": {"iterations": 12, "depth": 4}, "cap_w": 520.0,
    "measurement_noise": 0.01,
}
#: TINY under admission control at the controller's defaults
TINY_ADMISSION = dict(TINY, name="tiny-admission", admission={
    "lookahead_s": 30.0, "threshold": 1.0, "margin": 0.0, "defer": True})
SEED = 2**33 + 5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark with configuration and traffic files
    and their cells added, and no existing file edited: ``tiny-novel``,
    and ``tiny-tiered``, three tiers at ten times the pool's throughput
    under admission control."""
    root = tmp_path_factory.mktemp("bench")
    bench_dir = root / "chipbench"
    shutil.copytree(harness.ROOT / "chipbench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for config in (TINY, TINY_ADMISSION):
        name = config["name"]
        (bench_dir / "configs" / f"{name}.json").write_text(
            json.dumps(config))
        bench["configs"].append({"name": name, "source": config["source"],
                                 "file": f"chipbench/configs/{name}.json",
                                 "reduced": [], "why": "tests"})
    tiered = json.loads((bench_dir / "traffic" / "recur.json").read_text())
    tiered.update(utilization=10.0, tiers=TIERS)
    (bench_dir / "traffic" / "tiered.json").write_text(json.dumps(tiered))
    bench["workloads"] += [
        {"name": "tiny-novel", "config": "tiny", "traffic": "novel",
         "chips": 1, "why": "tests"},
        {"name": "tiny-tiered", "config": "tiny-admission",
         "traffic": "tiered", "chips": 1, "why": "tests"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture()
def small_waves(monkeypatch):
    import repro.core.prediction_service as ps

    monkeypatch.setattr(ps, "_on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_GBDT_KERNEL_MIN_ROWS", "48")


def keep_runs(mp: pytest.MonkeyPatch) -> list:
    """Every :class:`harness.Run` a metric reader is handed from now on,
    while ``mp`` holds."""
    seen = []
    read = harness.reader

    def reader(metrics_dir, name):
        def read_and_keep(run):
            seen.append(run)
            return read(metrics_dir, name)(run)
        return read_and_keep

    mp.setattr(harness, "reader", reader)
    return seen


@pytest.fixture()
def runs(monkeypatch):
    return keep_runs(monkeypatch)


def run(root, fault=None, name="tiny-novel"):
    cell = harness.load_cell(name, root=root)
    return harness.run_cell(cell, SEED, 1.0, False, time.perf_counter(),
                            check_chips=False, fault=fault)


def test_added_configuration_runs_and_is_correct(root, small_waves):
    out = run(root)
    assert out["correct"], out["checks"]
    assert out["checks"]["kernel_batches"]["value"] > 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"jobs_per_s", "place_p50_ms",
                                   "place_p99_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("mode", ["control", "half", "altered", "stale"])
def test_control_and_faults_fail(root, small_waves, mode):
    fault, undo = control.install(mode)
    try:
        out = run(root, fault)
    finally:
        undo()
    assert not out["correct"], out["checks"]


def test_added_tiered_deployment_is_correct_and_sheds_best_effort(
        root, small_waves, runs):
    out = run(root, name="tiny-tiered")
    assert out["correct"], out["checks"]
    assert out["checks"]["shed_unsheddable"]["value"] == 0
    parts = runs[0].parts
    shed = parts["result"].shed
    assert shed and {j.tier.name for j in shed} == {"best-effort"}
    assert parts["admission"].stats.shed == len(shed)
    assert out["failed"] == len(shed)


def test_shedding_and_parking_neither_raise_the_rate_nor_cut_the_wait(
        root, small_waves, runs):
    """The rate counts dispatched jobs only, and a job's wait starts at
    its first admission check: before the check returns, and so before a
    parked job's release."""
    checked, released = {}, {}

    def fault(_service, _coord, adm):
        check, release = adm.check, adm.release

        def timed_check(job, t, queue):
            ok = check(job, t, queue)
            checked.setdefault(job.job_id, time.perf_counter())
            return ok

        def timed_release(*a, **kw):
            out = release(*a, **kw)
            released.update((j.job_id, time.perf_counter()) for j in out)
            return out

        adm.check, adm.release = timed_check, timed_release

    out = run(root, fault, name="tiny-tiered")
    assert out["correct"], out["checks"]
    r = runs[0]
    result = r.parts["result"]
    assert r.shed == len(result.shed) > 0
    assert r.placed == len(result.records) == len(r.latencies_s)
    assert out["metrics"]["jobs_per_s"]["value"] == (
        len(result.records) / r.window_s)
    read = harness.reader(harness.HERE / "metrics", "shed_share")
    assert read(r) == 100 * r.shed / (r.placed + r.shed)
    assert released and set(checked) <= set(r.started)
    assert all(r.started[i] <= t for i, t in checked.items())
    assert all(r.started[i] < t for i, t in released.items())


def test_every_tier_sheddable_fails(root, small_waves):
    fault, undo = control.install("sheddable")
    try:
        out = run(root, fault, name="tiny-tiered")
    finally:
        undo()
    assert not out["correct"]
    assert out["checks"]["shed_unsheddable"]["value"] >= 1, out["checks"]


def test_without_admission_both_calls_are_todays(root, small_waves, runs,
                                                  monkeypatch):
    """The timed call and the scalar replay get the keyword arguments they
    had before admission could be configured, with ``admission=None``,
    the recorder off, and the stream of the mix's seeds."""
    import repro.core
    from repro.core import DEFAULT_TIER, tracing

    calls = []
    run_schedule = repro.core.run_schedule

    def recorded(jobs, *args, **kw):
        calls.append((kw, tracing.ON, jobs))
        return run_schedule(jobs, *args, **kw)

    monkeypatch.setattr(repro.core, "run_schedule", recorded)
    out = run(root)
    assert out["correct"], out["checks"]
    (timed, timed_on, _), (replay, replay_on, jobs) = calls
    assert not (timed_on or replay_on)
    assert timed["admission"] is None and replay["admission"] is None
    common = {"seed", "service", "device_classes", "power_coordinator",
              "admission"}
    assert set(timed) == common | {"hooks"}
    assert set(replay) == common | {"batch_decide"}
    assert replay["batch_decide"] is False
    assert runs[0].parts["admission"] is None
    assert runs[0].shed == 0
    assert harness.reader(harness.HERE / "metrics",
                          "shed_share")(runs[0]) is None

    traffic = harness.load_cell("tiny-novel", root=root)["traffic"]
    s_stream = traffic["stream_seed"]
    f = fixtures.build(TINY, SEED)
    want = gen.stream(f["suite"], f["testbed"], fixtures.pool_of(TINY),
                      traffic, seed=s_stream,
                      novel_seed=fixtures.sub_seed(s_stream, 2),
                      n_jobs=len(jobs))
    assert [fields(j) for j in jobs] == [fields(j) for j in want]
    assert {j.tier for j in jobs} == {DEFAULT_TIER}
