"""``rack_refresh_per_advance``: the facility coordinator's own counters,
read from the window's coordinator, on constructed runs and in a run of
the harness on the CPU; nothing where the coordinator keeps no such
counters."""
from types import SimpleNamespace

import numpy as np

from chipbench import harness
from chipbench.tests.test_runs import (  # noqa: F401 (fixtures)
    root, run, runs, small_waves)
from repro.core import FacilityCoordinator


def read(run_):
    return harness.reader(harness.HERE / "metrics",
                          "rack_refresh_per_advance")(run_)


def run_with(coord):
    return harness.Run(setup_s=1.0, window_s=1.0, placed=1,
                       latencies_s=np.zeros(0), compiles=0,
                       parts={"coordinator": coord})


def test_refreshes_per_advance():
    fac = FacilityCoordinator(1000.0, [2, 2, 2])
    fac.reset([20.0] * 6)
    fac.commit(0, 100.0, end=2.0, drawn_w=90.0)      # rack 0
    fac.commit(3, 100.0, end=3.0, drawn_w=90.0)      # rack 1
    fac.advance(1.0)                 # racks 0 and 1 changed: 2
    fac.advance(2.5)                 # rack 0 releases: 1
    fac.advance(2.6)                 # nothing: 0
    fac.advance(4.0)                 # rack 1 releases: 1
    assert read(run_with(fac)) == 4 / 4


def test_silent_without_the_counters():
    # a coordinator from before the counters, no coordinator, no advance
    before = SimpleNamespace(stats=SimpleNamespace(rebalances=3))
    assert read(run_with(before)) is None
    assert read(run_with(None)) is None
    fac = FacilityCoordinator(1000.0, [2, 2])
    fac.reset([20.0] * 4)
    assert read(run_with(fac)) is None


def test_a_window_reads_its_coordinators_counters(root, small_waves, runs):
    out = run(root)
    assert out["correct"], out["checks"]
    coord = runs[0].parts["coordinator"]
    assert coord.stats.advances >= runs[0].placed > 0
    value = read(runs[0])
    assert value == coord.stats.rack_refreshes / coord.stats.advances
    # three racks: between nothing changed and every rack changed
    assert 0 < value <= 3
