"""The schedule's guarantees, worked out from the records alone: they hold
on a schedule the program made, and each breaks when one record is
broken."""
import copy
import dataclasses

import pytest

from chipbench import reference
from repro.configs.paper_suite import PAPER_APPS
from repro.core import (V5E_CLASS, V5E_DVFS, FacilityCoordinator, Testbed,
                        make_device_pool, multi_rack_workload, run_schedule)
from repro.core.policies import Oracle

POOL = make_device_pool((V5E_CLASS, 8))
CAP = 900.0
SEED = 2**35 + 1


@pytest.fixture(scope="module")
def schedule():
    tb = Testbed(seed=3)
    jobs = list(multi_rack_workload(list(PAPER_APPS), tb, n_jobs=300,
                                    seed=4, utilization=0.5,
                                    device_classes=POOL))
    result = run_schedule(jobs, Oracle(V5E_DVFS), tb, seed=SEED,
                          device_classes=POOL,
                          power_coordinator=FacilityCoordinator(
                              CAP, [4, 4]))
    return jobs, result


def held(jobs, result):
    return reference.guarantees(result, jobs, POOL, CAP, SEED, 0.01)


def test_a_program_schedule_holds_every_guarantee(schedule):
    jobs, result = schedule
    assert held(jobs, result) == {
        "jobs_misplaced": 0, "overlaps": 0, "physics_differ": 0,
        "misses_recount": 0, "grants_missing": 0, "cap_excess_w": 0.0,
        "shed_unsheddable": 0}


def broken(result, i, **change):
    out = copy.copy(result)
    out.records = list(result.records)
    out.records[i] = dataclasses.replace(result.records[i], **change)
    return out


@pytest.mark.parametrize("name,change", [
    ("jobs_misplaced", lambda r, rs: {"job_id": rs[0].job_id}),
    ("overlaps", lambda r, rs: {"start": r.arrival - 1.0}),
    ("physics_differ", lambda r, rs: {"power_w": r.power_w * (1 + 1e-12)}),
    ("physics_differ", lambda r, rs: {"clock": next(
        c for c in V5E_DVFS.clock_list() if c != r.clock)}),
    ("grants_missing", lambda r, rs: {"power_grant_w": None}),
    ("cap_excess_w", lambda r, rs: {"power_grant_w": CAP}),
])
def test_one_broken_record_breaks_a_guarantee(schedule, name, change):
    jobs, result = schedule
    i = len(result.records) // 2
    r = result.records[i]
    out = held(jobs, broken(result, i, **change(r, result.records)))
    assert out[name] > 0, out


def test_a_missed_deadline_must_be_counted(schedule):
    jobs, result = schedule
    out = copy.copy(result)
    out.records = [dataclasses.replace(r, met_deadline=True)
                   for r in result.records]
    assert result.misses > 0
    assert held(jobs, out)["misses_recount"] == result.misses


def test_a_shed_job_must_be_of_a_sheddable_tier(schedule):
    jobs, result = schedule
    out = copy.copy(result)
    out.records = [r for r in result.records if r.job_id != jobs[0].job_id]
    out.shed = [jobs[0]]
    assert held(jobs, out)["jobs_misplaced"] == 0
    assert held(jobs, out)["shed_unsheddable"] == 1
    assert reference.guarantees(out, jobs, POOL, CAP, SEED, 0.01,
                                {jobs[0].tier.name})["shed_unsheddable"] == 0
