"""Set-up of one cell from its configuration file and the run's seed: the
fleet, the profiled app suite and the fitted predictor.

A frozen copy of the profiling campaign of ``benchmarks/common.fixtures``
(paper apps profiled and fitted on a V5E testbed) and of the model-app
registration that ``chip_smoke.py`` adds to it, with every seed drawn from
the run's seed. The program's own calls (``build_dataset``,
``profile_features``, ``EnergyTimePredictor.fit``) are used as a user of
the system uses them.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.configs.paper_suite import PAPER_APPS
from repro.core import (DEVICE_CLASSES, EnergyTimePredictor, PredictorConfig,
                        Testbed, build_dataset, model_app_suite,
                        profile_features)


def sub_seed(seed: int, tag: int) -> int:
    """A 32-bit seed for one use (``tag``) drawn from a run seed of any
    size."""
    return int(np.random.SeedSequence([int(seed) % 2**64, tag])
               .generate_state(1)[0])


def pool_of(config: dict) -> list:
    """The positional device pool: one DeviceClass per device."""
    pool = []
    for name, count in config["pool"]:
        pool.extend([DEVICE_CLASSES[name]] * int(count))
    return pool


def predictor_config(config: dict) -> PredictorConfig:
    """The paper's CatBoost-role regressors at the configuration's size."""
    base = PredictorConfig()
    kw = {k: int(config["predictor"][k]) for k in ("iterations", "depth")}
    return dataclasses.replace(
        base, gbdt=dataclasses.replace(base.gbdt, **kw),
        gbdt_time=dataclasses.replace(base.gbdt_time, **kw))


def build(config: dict, seed: int) -> dict:
    """Profile the suite and fit the predictor, all from ``seed``."""
    s_bed, s_data, s_feat, s_model = (sub_seed(seed, k)
                                      for k in (10, 11, 12, 13))
    tb = Testbed(noise=float(config["measurement_noise"]), seed=s_bed)
    paper = list(PAPER_APPS)
    X, y_power, y_time, _ = build_dataset(paper, tb, seed=s_data)
    rng = np.random.default_rng(s_feat)
    feats = {a.name: profile_features(a, tb, rng=rng) for a in paper}
    suite = list(paper)
    if config.get("model_apps", False):
        model = list(model_app_suite())
        for i, app in enumerate(model):
            feats[app.name] = profile_features(
                app, tb, rng=np.random.default_rng([s_model, i]))
        suite += model
    predictor = EnergyTimePredictor(predictor_config(config)).fit(
        X, y_power, y_time)
    return {"testbed": tb, "suite": suite, "features": feats,
            "predictor": predictor}
