"""The traffic generator: a frozen copy of the program's multi-rack stream,
and a deterministic maker of never-profiled apps."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import gen
from repro.configs.paper_suite import PAPER_APPS
from repro.core import (V5E_CLASS, V5LITE_CLASS, V5P_CLASS, Testbed,
                        make_device_pool, multi_rack_workload)

TRAFFIC = pathlib.Path(gen.__file__).resolve().parent / "traffic"
MIXED = make_device_pool((V5P_CLASS, 2), (V5E_CLASS, 4), (V5LITE_CLASS, 2))
V5E = [V5E_CLASS] * 16


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def fields(job):
    return (job.app, job.arrival, job.deadline, job.job_id,
            job.checkpoint_quantum, job.tier)


@pytest.mark.parametrize("pool", [V5E, MIXED], ids=["v5e", "mixed"])
@pytest.mark.parametrize("seed", [load("recur")["stream_seed"], 7,
                                  2**40 + 3])
def test_recur_equals_multi_rack_workload_job_for_job(pool, seed):
    tb = Testbed(seed=1)
    suite = list(PAPER_APPS)
    traffic = load("recur")
    n = 20 * len(pool) + 3              # ends inside a burst
    got = list(gen.stream(suite, tb, pool, traffic, seed=seed, n_jobs=n))
    want = list(multi_rack_workload(
        suite, tb, n_jobs=n, seed=seed,
        slack_range=tuple(traffic["slack_range"]),
        utilization=traffic["utilization"],
        quantum_frac=traffic["quantum_frac"], device_classes=pool))
    assert len(got) == n
    assert [fields(j) for j in got] == [fields(j) for j in want]


@pytest.mark.parametrize("mix", ["novel", "trickle"])
def test_novel_share_keeps_the_arrivals_and_base_apps(mix):
    tb = Testbed(seed=1)
    suite = list(PAPER_APPS)
    base = list(gen.stream(suite, tb, V5E, load("recur"), seed=5,
                           n_jobs=2000))
    new = []
    mixed = list(gen.stream(suite, tb, V5E, load(mix), seed=5,
                            novel_seed=9, on_novel=new.append,
                            n_jobs=2000))
    assert [j.arrival for j in mixed] == [j.arrival for j in base]
    novel = [j for j in mixed if j.app.name.startswith("novel-")]
    assert [j.app for j in novel] == new
    share = load(mix)["novel_share"]
    assert abs(len(novel) / len(mixed) - share) < 0.05
    for j, b in zip(mixed, base):
        if j.app.name.startswith("novel-"):
            assert (j.app.flops, j.app.hbm_bytes) == (b.app.flops,
                                                      b.app.hbm_bytes)
        else:
            assert fields(j)[:2] == fields(b)[:2]


def test_novel_apps_are_deterministic_in_the_seed():
    lat = load("novel")["novel_latents"]
    base = PAPER_APPS[3]

    def make(seed):
        rng = np.random.default_rng(seed)
        return [gen.novel_app(base, i, rng, lat) for i in range(5)]

    assert make(11) == make(11)
    assert make(11) != make(12)
    for app in make(11):
        for key in ("stall_frac", "core_eff", "mem_eff"):
            lo, hi = lat[key]
            assert lo <= getattr(app, key) <= hi


def test_stop_ends_the_stream():
    tb = Testbed(seed=1)
    n = [0]

    def stop():
        n[0] += 1
        return n[0] > 50

    jobs = list(gen.stream(list(PAPER_APPS), tb, V5E, load("recur"),
                           seed=1, stop=stop))
    assert len(jobs) == 50
