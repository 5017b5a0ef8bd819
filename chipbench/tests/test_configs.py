"""The configuration files: each fixed cap follows its recorded rule, and
the frozen physics copy agrees with the program's simulator today."""
import json
import pathlib

import pytest

from chipbench import fixtures, physics
from repro.configs.paper_suite import PAPER_APPS
from repro.core import DEVICE_CLASSES, Testbed

CONFIGS = sorted((pathlib.Path(physics.__file__).resolve().parent
                  / "configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_cap_follows_its_rule(path):
    config = json.loads(path.read_text())
    pool = fixtures.pool_of(config)
    peak = sum(physics.peak_power(c.dvfs) for c in pool)
    assert config["cap_w"] == pytest.approx(config["cap_frac"] * peak,
                                            rel=1e-12)
    assert sum(config["racks"]) == len(pool)
    assert len(config["source"]) <= 200


@pytest.mark.parametrize("cls", sorted(DEVICE_CLASSES))
def test_physics_copy_equals_the_simulator(cls):
    d = DEVICE_CLASSES[cls].dvfs
    tb = Testbed(dvfs=d)
    for app in PAPER_APPS:
        for clock in d.clock_list()[::7]:
            assert physics.true_time(app, clock, d) == tb.true_time(app,
                                                                    clock)
            assert physics.true_power(app, clock, d) == tb.true_power(
                app, clock)
    assert physics.peak_power(d) == d.power(d.max_clock, 1.0, 1.0)
