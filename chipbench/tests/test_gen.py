"""The traffic generator: a frozen copy of the program's multi-rack stream,
a deterministic maker of never-profiled apps, and SLA tiers stamped from
a mix's data."""
import hashlib
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
#: three SLA tiers in the shares and slack ranges of the program's default
#: tenant mix, as a mix's data
TIERS = [
    {"name": "slo", "priority": 2, "weight": 4.0, "sheddable": False,
     "slack_range": [0.25, 1.0], "share": 0.10},
    {"name": "batch", "priority": 1, "weight": 2.0, "sheddable": False,
     "slack_range": [2.0, 6.0], "share": 0.15},
    {"name": "best-effort", "priority": 0, "weight": 1.0,
     "sheddable": True, "slack_range": [6.0, 16.0], "share": 0.75},
]


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


def digest(jobs) -> str:
    """sha256 of every job's app, times, id, quantum and tier, floats in
    hex."""
    h = hashlib.sha256()
    for j in jobs:
        a = j.app
        h.update(repr((a.name, a.seed, a.stall_frac.hex(), a.core_eff.hex(),
                       a.mem_eff.hex(), j.arrival.hex(), j.deadline.hex(),
                       j.job_id, j.checkpoint_quantum.hex(), j.tier.name,
                       )).encode())
    return h.hexdigest()


#: the streams of the mixes with new apps as the generator drew them before
#: tiers could be listed (16 v5e, seed 5, new apps from seed 9, 2,000 jobs)
DIGESTS = {
    "novel": "5f8d62133cf859c6241f2db1213df6a7"
             "fed32de8d0c251adae978c6de713a1e2",
    "trickle": "7ba401264f09871aa8ac0c37ae510edec"
               "637360f87169e016488f70007ca79a4",
}


@pytest.mark.parametrize("mix", sorted(DIGESTS))
def test_untiered_mix_is_unchanged_job_for_job(mix):
    jobs = gen.stream(list(PAPER_APPS), Testbed(seed=1), V5E, load(mix),
                      seed=5, novel_seed=9, n_jobs=2000)
    assert digest(jobs) == DIGESTS[mix]


def test_tiers_keep_the_stream_and_anchor_deadlines_at_arrival():
    tb = Testbed(seed=1)
    suite = list(PAPER_APPS)
    n = 4000
    base = list(gen.stream(suite, tb, V5E, load("novel"), seed=5,
                           novel_seed=9, n_jobs=n))
    mix = dict(load("novel"), tiers=TIERS)
    tiered = list(gen.stream(suite, tb, V5E, mix, seed=5, novel_seed=9,
                             tier_seed=13, n_jobs=n))
    assert [(j.app, j.arrival, j.job_id, j.checkpoint_quantum)
            for j in tiered] == [(j.app, j.arrival, j.job_id,
                                  j.checkpoint_quantum) for j in base]
    d = V5E_CLASS.dvfs
    for spec in TIERS:
        mine = [j for j in tiered if j.tier.name == spec["name"]]
        p = spec["share"]
        assert abs(len(mine) / n - p) <= 4 * np.sqrt(p * (1 - p) / n)
        assert {(j.tier.priority, j.tier.weight, j.tier.sheddable,
                 j.tier.slack_range) for j in mine} == {
            (spec["priority"], spec["weight"], spec["sheddable"],
             tuple(spec["slack_range"]))}
        lo, hi = spec["slack_range"]
        for j in mine:
            t_a = tb.true_time(j.app, d.default_clock, dvfs=d)
            assert (j.arrival + (1 + lo) * t_a * (1 - 1e-12) <= j.deadline
                    <= j.arrival + (1 + hi) * t_a * (1 + 1e-12))
    other = list(gen.stream(suite, tb, V5E, mix, seed=5, novel_seed=9,
                            tier_seed=14, n_jobs=n))
    assert [j.tier for j in other] != [j.tier for j in tiered]
