"""Whole runs of a cell on the CPU at a small size: a configuration added
as a file runs without any other edit, the comparison passes on the
program as it is, and it fails on the control and on each fault.

The look for a chip is skipped, the kernel runs in interpret mode, and the
routing threshold is lowered so that small waves reach the kernel.
"""
import json
import shutil
import time

import pytest

from chipbench import control, harness

TINY = {
    "name": "tiny", "source": "a small fleet for tests", "reduced": [],
    "pool": [["v5p", 2], ["v5e", 4], ["v5lite", 2]], "racks": [2, 4, 2],
    "coordinator": {"share_policy": "demand-weighted",
                    "grant_policy": "slack-weighted", "guard": 0.2},
    "policy": {"name": "risk-aware", "margin": 0.05}, "model_apps": False,
    "predictor": {"iterations": 12, "depth": 4}, "cap_w": 520.0,
    "measurement_noise": 0.01,
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout of the benchmark with one configuration file and one
    cell added, and no existing file edited."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(harness.ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": TINY["source"],
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": "tiny-novel", "config": "tiny",
                               "traffic": "novel", "chips": 1,
                               "why": "tests"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture()
def small_waves(monkeypatch):
    import repro.core.prediction_service as ps

    monkeypatch.setattr(ps, "_on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_GBDT_KERNEL_MIN_ROWS", "48")


def run(root, fault=None):
    cell = harness.load_cell("tiny-novel", root=root)
    return harness.run_cell(cell, 2**33 + 5, 1.0, False, time.perf_counter(),
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
