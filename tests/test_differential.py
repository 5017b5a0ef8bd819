"""Differential / property harness for the preemptive engine (PR 5).

Two complementary nets over the segmented dispatch loop:

* **Differential identity** — hypothesis-generated random (pool, workload,
  policy, cap, quantum) configurations, run through the *segmented* engine
  with a trigger-disabled :class:`~repro.core.preemption.PreemptionManager`
  (boundaries are visited, every verdict declines) and through the plain
  engine: the record streams must be **bit-identical**. This is the
  strongest statement that segmentation itself is free — admissions,
  budgets, feedback delivery, cap grants, and the RNG stream all line up.
* **Conservation properties** — with triggers armed on the rescue-stress
  stream: work is never lost or double-run (Σ segment ``work_frac`` per
  job is exactly 1, segments contiguous with exactly one final record),
  billed energy decomposes exactly into duration x draw + explicit
  overhead joules, and per-device segments never overlap across
  preemption events.

Plus the satellite coverage this PR hardens:

* ``BudgetManager.snapshot/restore`` under repeated deferral+preemption
  interleavings (rollback round-trips compose — the capped engine's
  deferral path and the preemptive remnant re-admissions exercise the
  same contract);
* :class:`~repro.core.powercap.PowerTelemetry` ledgers over schedules
  containing *split* busy intervals from preempted segments (integrals
  stay exact, steps stay nonnegative, grants stay under the cap).

Runs with or without the real ``hypothesis`` package — the deterministic
shim in ``_hypothesis_fallback`` honors the ``@settings`` kwargs and
strategies used here, so the suite collects identically either way.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # not installed in this container — deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs.paper_suite import PAPER_APPS
from repro.core import (
    BATCH_TIER, BEST_EFFORT_TIER, DEFAULT_TIER, EnergyTimePredictor,
    FacilityCoordinator, FederatedPreemptionManager, Job,
    PowerCapCoordinator, PowerTelemetry, PredictorConfig, PreemptionConfig,
    PreemptionManager, SLO_TIER, Testbed, V5E_CLASS, V5E_DVFS, V5LITE_CLASS,
    V5P_CLASS, build_dataset, edf_key, merge_workloads, model_app_suite,
    multi_rack_workload, profile_features, register_model_apps,
    rescue_stress_workload, run_schedule, serving_workload, stream_workload,
    training_workload,
)
from repro.core.gbdt import GBDTParams
from repro.core.policies import (MinEnergy, POLICY_NAMES, QueueAwareBudget,
                                 VirtualPacingBudget)
from repro.core.prediction_service import ClockTable

APPS = list(PAPER_APPS)[:6]
SMALL = PredictorConfig(
    gbdt=GBDTParams(iterations=60, depth=3, learning_rate=0.15,
                    l2_leaf_reg=5.0),
    gbdt_time=GBDTParams(iterations=60, depth=3, learning_rate=0.15,
                         l2_leaf_reg=3.0),
)

#: Pool shapes the differential sweep draws from: classless single/multi
#: device, a uniform explicit pool, and a mixed pool (joint placement).
_POOLS: tuple = (
    ("classless-1", None, 1),
    ("classless-2", None, 2),
    ("uniform-v5e", [V5E_CLASS] * 3, 3),
    ("mixed", [V5P_CLASS, V5E_CLASS, V5LITE_CLASS], 3),
)
#: Cap regimes: uncoordinated, coordinated-but-infinite, binding.
_CAPS = ("none", "inf", "binding")

#: Trigger-disabled config: boundaries are visited, verdicts all decline.
_OFF = PreemptionConfig(self_rescue=False, queue_rescue=False)
#: Armed config, tuned eager so conservation tests see real preemptions.
_ARMED = PreemptionConfig(margin=0.02, min_remnant_frac=0.02)


@functools.lru_cache(maxsize=1)
def _fixture():
    tb = Testbed(seed=0)
    X, yp, yt, _ = build_dataset(APPS, tb, seed=0)
    rng = np.random.default_rng(7)
    return {
        "testbed": tb,
        "predictor": EnergyTimePredictor(SMALL).fit(X, yp, yt),
        "features": {a.name: profile_features(a, tb, rng=rng)
                     for a in APPS},
    }


def _jobs(seed: int, pool_idx: int, quantum: float) -> list[Job]:
    """A quantum-carrying job list: the Poisson stream with every job made
    interruptible (quantum scaled off its own DC slack)."""
    f = _fixture()
    _, _, n_dev = _POOLS[pool_idx]
    jobs = list(stream_workload(APPS, f["testbed"], n_jobs=30, seed=seed,
                                n_devices=n_dev))
    return [dataclasses.replace(j, checkpoint_quantum=quantum)
            for j in jobs]


#: SLA tiers the multi-tenant fuzz assigns at random (PR 7) — includes
#: the default tier so runs mix tagged and untagged work.
_TIER_CHOICES = (SLO_TIER, BATCH_TIER, BEST_EFFORT_TIER, DEFAULT_TIER)


def _tiered(jobs: list[Job], tier_seed: int) -> list[Job]:
    """Deterministic random tier assignment over an existing stream."""
    rng = np.random.default_rng(tier_seed)
    picks = rng.integers(0, len(_TIER_CHOICES), size=len(jobs))
    return [dataclasses.replace(j, tier=_TIER_CHOICES[int(k)])
            for j, k in zip(jobs, picks)]


def _coordinator(cap_kind: str, jobs, pool_idx: int, policy: str):
    """None, an infinite coordinator, or one binding at 60% of this
    configuration's uncapped peak headroom."""
    if cap_kind == "none":
        return None
    if cap_kind == "inf":
        return PowerCapCoordinator(math.inf, guard=0.15)
    f = _fixture()
    name, pool, n_dev = _POOLS[pool_idx]
    r0 = _run(jobs, pool_idx, policy, coordinator=None, preemption=None)
    if pool is not None:
        led = PowerTelemetry.from_result(r0, pool=pool)
        idle = sum(c.idle_power() for c in pool)
    else:
        idle_w = f["testbed"].idle_power()
        led = PowerTelemetry.from_result(r0, idle_powers=idle_w,
                                         n_devices=n_dev)
        idle = idle_w * n_dev
    cap = idle + 0.6 * max(led.peak_w - idle, 1.0)
    return PowerCapCoordinator(cap, grant_policy="slack-weighted",
                               guard=0.15)


def _run(jobs, pool_idx: int, policy: str, coordinator, preemption):
    f = _fixture()
    _, pool, n_dev = _POOLS[pool_idx]
    return run_schedule(
        jobs, policy, Testbed(seed=1000),
        predictor=f["predictor"], app_features=f["features"],
        n_devices=n_dev, device_classes=pool,
        power_coordinator=coordinator, preemption=preemption)


def _assert_identical(a, b):
    assert len(a.records) == len(b.records)
    for i, (ra, rb) in enumerate(zip(a.records, b.records)):
        assert ra == rb, (i, ra, rb)


# ---------------------------------------------------------------------- #
#  Differential identity: segmented-but-never-preempted == plain engine
# ---------------------------------------------------------------------- #
class TestDifferentialIdentity:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 50),
           pool_idx=st.integers(0, len(_POOLS) - 1),
           policy=st.sampled_from(list(POLICY_NAMES)),
           quantum=st.floats(0.05, 2.0))
    def test_segmented_never_preempted_is_bit_identical(
            self, seed, pool_idx, policy, quantum):
        """Random (seed, pool, policy, quantum): a trigger-disabled
        manager visits every boundary yet reproduces the plain engine's
        records bit-for-bit (compare= fields included)."""
        jobs = _jobs(seed, pool_idx, quantum)
        a = _run(jobs, pool_idx, policy, None, None)
        mgr = PreemptionManager(_OFF)
        b = _run(jobs, pool_idx, policy, None, mgr)
        _assert_identical(a, b)
        assert mgr.stats.preemptions == 0
        assert all(r.segment == 0 and not r.preempted for r in b.records)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 50),
           pool_idx=st.integers(0, len(_POOLS) - 1),
           policy=st.sampled_from(list(POLICY_NAMES)),
           quantum=st.floats(0.05, 2.0),
           tier_seed=st.integers(0, 1000))
    def test_tiered_segmented_never_preempted_is_bit_identical(
            self, seed, pool_idx, policy, quantum, tier_seed):
        """PR 7: the same identity with random SLA tiers on every job —
        tier-priority queue keys and tier-weighted urgencies reorder
        work, but a preemption-disabled multi-tenant run must still be
        bit-identical to the plain (manager-less) engine on the same
        tiered stream, and no tier rescue may fire."""
        jobs = _tiered(_jobs(seed, pool_idx, quantum), tier_seed)
        a = _run(jobs, pool_idx, policy, None, None)
        mgr = PreemptionManager(_OFF)
        b = _run(jobs, pool_idx, policy, None, mgr)
        _assert_identical(a, b)
        assert mgr.stats.preemptions == 0
        assert mgr.stats.tier_rescues == 0
        assert all(r.segment == 0 and not r.preempted for r in b.records)

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 50),
           pool_idx=st.integers(0, len(_POOLS) - 1),
           policy=st.sampled_from(["min-energy", "d-dvfs", "dc"]),
           cap_kind=st.sampled_from(list(_CAPS)),
           quantum=st.floats(0.05, 1.5))
    def test_identity_holds_under_power_caps(
            self, seed, pool_idx, policy, cap_kind, quantum):
        """The same identity through the coordinated paths: offers,
        ladder filtering, escalation, and deferral all happen at the same
        decisions with the same grants."""
        jobs = _jobs(seed, pool_idx, quantum)
        coord_a = _coordinator(cap_kind, jobs, pool_idx, policy)
        coord_b = _coordinator(cap_kind, jobs, pool_idx, policy)
        a = _run(jobs, pool_idx, policy, coord_a, None)
        b = _run(jobs, pool_idx, policy, coord_b, PreemptionManager(_OFF))
        _assert_identical(a, b)

    @pytest.mark.parametrize("policy", POLICY_NAMES)
    @pytest.mark.parametrize("pool_idx", range(len(_POOLS)),
                             ids=[p[0] for p in _POOLS])
    def test_exhaustive_all_policies_all_pools(self, policy, pool_idx):
        """The acceptance grid, exhaustively (not sampled): every policy
        × classless / uniform / mixed pools, uncapped and under a
        binding cap, with a segmented-but-never-preempting manager —
        records bit-identical to the plain engine."""
        jobs = _jobs(3, pool_idx, 0.3)
        for cap_kind in ("none", "binding"):
            coord_a = _coordinator(cap_kind, jobs, pool_idx, policy)
            coord_b = _coordinator(cap_kind, jobs, pool_idx, policy)
            a = _run(jobs, pool_idx, policy, coord_a, None)
            b = _run(jobs, pool_idx, policy, coord_b,
                     PreemptionManager(_OFF))
            _assert_identical(a, b)

    def test_boundaries_are_actually_visited(self):
        """The identity above must not be vacuous: on a stream of
        interruptible jobs the disabled manager really does visit
        segment boundaries (and declines every one)."""
        jobs = _jobs(0, 0, 0.1)
        mgr = PreemptionManager(_OFF)
        _run(jobs, 0, "min-energy", None, mgr)
        assert mgr.stats.boundaries > 0
        assert mgr.stats.preemptions == 0

    @pytest.mark.parametrize("pool_idx", [0, 1, 3],
                             ids=[_POOLS[i][0] for i in (0, 1, 3)])
    def test_identity_with_feedback_attached(self, pool_idx):
        """The segmented loop's deferred feedback delivery (fb_seq
        assigned at dispatch, records finalized at completion or by an
        early drain) must hand the OnlineAdapter the same observation
        stream as the plain loop — corrected tables, and therefore every
        decision, stay bit-identical when no boundary fires."""
        from repro.core import OnlineAdapter, PredictionService
        f = _fixture()
        _, pool, n_dev = _POOLS[pool_idx]
        jobs = _jobs(2, pool_idx, 0.2)
        results = []
        for mgr in (None, PreemptionManager(_OFF)):
            svc = PredictionService(V5E_DVFS, predictor=f["predictor"],
                                    app_features=f["features"],
                                    testbed=f["testbed"])
            adapter = OnlineAdapter(svc)
            results.append((run_schedule(
                jobs, "min-energy", Testbed(seed=1000), service=svc,
                n_devices=n_dev, device_classes=pool, feedback=adapter,
                preemption=mgr), adapter))
        (a, ad_a), (b, ad_b) = results
        _assert_identical(a, b)
        assert ad_a.n_observed == ad_b.n_observed == len(a.records)

    def test_feedback_observes_per_segment(self):
        """With rescues armed and an adapter attached, every segment is
        a feedback observation (the per-segment residual normalization
        path) — preemptions don't starve the measurement loop."""
        from repro.core import OnlineAdapter, PredictionService
        f = _fixture()
        jobs = list(rescue_stress_workload(APPS, f["testbed"], n_jobs=36,
                                           seed=0, n_devices=1))
        svc = PredictionService(V5E_DVFS, predictor=f["predictor"],
                                app_features=f["features"],
                                testbed=f["testbed"])
        adapter = OnlineAdapter(svc)
        r = run_schedule(jobs, "min-energy", Testbed(seed=1000),
                         service=svc, feedback=adapter,
                         preemption=PreemptionManager(_ARMED))
        assert r.preemptions > 0
        # every segment with real execution time is observed (truncated
        # checkpoint-only slivers may be skipped — count those out)
        slivers = sum(1 for x in r.records
                      if x.work_frac <= 1e-9
                      or x.time_s - x.overhead_s <= 0)
        assert adapter.n_observed == len(r.records) - slivers
        assert adapter.n_observed > len(jobs)     # segments > jobs


# ---------------------------------------------------------------------- #
#  Conservation: work and energy, with triggers armed
# ---------------------------------------------------------------------- #
def _preemptive_run(seed: int, n_devices: int, cap_kind: str = "none"):
    f = _fixture()
    jobs = list(rescue_stress_workload(APPS, f["testbed"], n_jobs=36,
                                       seed=seed, n_devices=n_devices))
    coord = None
    if cap_kind == "binding":
        r0 = run_schedule(jobs, "min-energy", Testbed(seed=1000),
                          predictor=f["predictor"],
                          app_features=f["features"], n_devices=n_devices)
        idle = f["testbed"].idle_power() * n_devices
        led = PowerTelemetry.from_result(
            r0, idle_powers=f["testbed"].idle_power(),
            n_devices=n_devices)
        coord = PowerCapCoordinator(
            idle + 0.65 * max(led.peak_w - idle, 1.0), guard=0.15)
    mgr = PreemptionManager(_ARMED)
    r = run_schedule(jobs, "min-energy", Testbed(seed=1000),
                     predictor=f["predictor"], app_features=f["features"],
                     n_devices=n_devices, power_coordinator=coord,
                     preemption=mgr)
    return jobs, r, mgr, coord


class TestConservation:
    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 20), n_devices=st.integers(1, 3))
    def test_work_never_lost_or_double_run(self, seed, n_devices):
        jobs, r, mgr, _ = self._checked(seed, n_devices)
        by_job: dict[int, list] = {}
        for rec in r.records:
            by_job.setdefault(rec.job_id, []).append(rec)
        assert sorted(by_job) == sorted(j.job_id for j in jobs)
        for jid, recs in by_job.items():
            # Σ work_frac == 1: remnant work neither lost nor repeated
            assert math.fsum(x.work_frac for x in recs) == pytest.approx(
                1.0, abs=1e-9), jid
            # segments contiguous 0..k in start-time order, exactly one
            # final (non-preempted) record, and it is the last
            recs.sort(key=lambda x: x.start)
            assert [x.segment for x in recs] == list(range(len(recs)))
            assert [x.preempted for x in recs] == \
                [True] * (len(recs) - 1) + [False]

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 20), n_devices=st.integers(1, 3))
    def test_energy_decomposes_exactly(self, seed, n_devices):
        """Billed energy = duration x measured draw + explicit overhead
        joules, per record — so summed segment energies are the job's
        whole bill, checkpoint/restore included."""
        _, r, _, _ = self._checked(seed, n_devices)
        for rec in r.records:
            assert rec.energy_j == pytest.approx(
                rec.time_s * rec.power_w + rec.overhead_j, rel=1e-12)
            assert rec.time_s == pytest.approx(rec.end - rec.start,
                                               rel=1e-12)
            assert 0.0 <= rec.work_frac <= 1.0 + 1e-12

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 20), n_devices=st.integers(1, 3))
    def test_no_device_overlap_across_preemptions(self, seed, n_devices):
        _, r, _, _ = self._checked(seed, n_devices)
        by_dev: dict[int, list] = {}
        for rec in r.records:
            by_dev.setdefault(rec.device, []).append((rec.start, rec.end))
        for spans in by_dev.values():
            spans.sort()
            for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
                assert s2 >= e1 - 1e-9

    _cache: dict = {}

    def _checked(self, seed, n_devices):
        key = (seed, n_devices)
        if key not in self._cache:
            self._cache[key] = _preemptive_run(seed, n_devices)
        return self._cache[key]

    def test_preemptions_actually_happen(self):
        """The conservation net must not be vacuous."""
        fired = 0
        for seed in range(4):
            _, r, _, _ = self._checked(seed, 1)
            fired += r.preemptions
        assert fired > 0

    def test_misses_counted_per_job_not_per_segment(self):
        _, r, _, _ = self._checked(0, 1)
        finals = r.final_records()
        assert len(finals) == len({x.job_id for x in r.records})
        assert r.misses == sum(not x.met_deadline for x in finals)
        assert r.misses <= len(finals)


# ---------------------------------------------------------------------- #
#  Power cap x preemption: grants shrink at boundaries, ledger exact
# ---------------------------------------------------------------------- #
#  Federation (PR 9): cross-rack migration keeps conservation discipline
# ---------------------------------------------------------------------- #
def _federated_run(seed: int):
    """A 2x2-device federation with one injected slow device: binding
    facility cap, demand-weighted shares, straggler rescue armed."""
    f = _fixture()
    jobs = list(multi_rack_workload(APPS, f["testbed"], n_devices=4,
                                    n_jobs=40, seed=seed))
    r0 = run_schedule(jobs, "min-energy", Testbed(seed=1000),
                      predictor=f["predictor"], app_features=f["features"],
                      n_devices=4)
    idle = f["testbed"].idle_power() * 4
    led = PowerTelemetry.from_result(r0, idle_powers=f["testbed"].idle_power(),
                                     n_devices=4)
    fed = FacilityCoordinator(idle + 0.7 * max(led.peak_w - idle, 1.0),
                              [2, 2], share_policy="demand-weighted",
                              guard=0.15)
    pre = FederatedPreemptionManager([2, 2], config=_ARMED,
                                     dvfs=f["testbed"].dvfs,
                                     device_slowdown={1: 2.5})
    r = run_schedule(jobs, "min-energy", Testbed(seed=1000),
                     predictor=f["predictor"], app_features=f["features"],
                     n_devices=4, power_coordinator=fed, preemption=pre)
    return jobs, r, fed, pre


class TestFederatedMigration:
    _cache: dict = {}

    def _run(self, seed):
        if seed not in self._cache:
            self._cache[seed] = _federated_run(seed)
        return self._cache[seed]

    def test_conservation_spans_racks(self):
        """Σ work_frac == 1 per job even when its segments land on
        different racks; migrated segments are always remnants."""
        for seed in range(3):
            jobs, r, _, _ = self._run(seed)
            by_job: dict[int, list] = {}
            for rec in r.records:
                by_job.setdefault(rec.job_id, []).append(rec)
            assert sorted(by_job) == sorted(j.job_id for j in jobs)
            for jid, recs in by_job.items():
                assert math.fsum(x.work_frac for x in recs) == \
                    pytest.approx(1.0, abs=1e-9), (seed, jid)
            for rec in r.records:
                if rec.migrated:
                    assert rec.segment > 0
                    assert rec.rack is not None

    def test_migration_counters_consistent(self):
        """``migrations`` == migrated records == Σ per-rack counts, and
        each migrated segment really changed racks vs its predecessor."""
        total = 0
        for seed in range(3):
            _, r, _, _ = self._run(seed)
            migrated = [x for x in r.records if x.migrated]
            assert r.migrations == len(migrated)
            by_rack = r.migrations_by_rack()
            assert sum(by_rack.values()) == r.migrations
            prev_rack = {}
            for rec in sorted(r.records, key=lambda x: (x.job_id,
                                                        x.segment)):
                if rec.migrated:
                    assert prev_rack[rec.job_id] != rec.rack, rec
                    assert by_rack.get(rec.rack, 0) > 0
                prev_rack[rec.job_id] = rec.rack
            total += r.migrations
        assert total > 0  # the net is not vacuous

    def test_plain_runs_report_zero_migrations(self):
        """Non-federated schedules never invent migrations: counters are
        zero and the per-rack map is empty (rack provenance absent)."""
        _, r, _, _ = _preemptive_run(0, 2)
        assert r.migrations == 0
        assert r.migrations_by_rack() == {}
        assert all(x.rack is None for x in r.records)


# ---------------------------------------------------------------------- #
class TestCappedPreemption:
    def test_granted_ledger_stays_under_cap_with_preemption(self):
        """Preempted grants are truncated at the boundary; the
        granted-view ledger built from split records must still never sum
        above the cap, and the measured ledger's integral must stay
        exactly Σ busy + idle energy."""
        f = _fixture()
        for seed in range(3):
            _, r, _, coord = _preemptive_run(seed, 2, cap_kind="binding")
            idle_w = f["testbed"].idle_power()
            for view in ("measured", "granted"):
                led = PowerTelemetry.from_result(
                    r, idle_powers=idle_w, n_devices=2, view=view)
                assert led.peak_w <= coord.cap_w + 1e-6, (seed, view)

    def test_split_interval_ledger_integral_exact(self):
        """Telemetry over a schedule with preempted (split) busy
        intervals: the step function integrates exactly to Σ record
        draw x duration + idle energy — no discretization error from the
        extra breakpoints, and every step nonnegative."""
        f = _fixture()
        _, r, _, _ = self._split_run()
        idle_w = f["testbed"].idle_power()
        n_dev = 2
        led = PowerTelemetry.from_result(r, idle_powers=idle_w,
                                         n_devices=n_dev)
        horizon = max(x.end for x in r.records)
        busy = math.fsum(x.power_w * (x.end - x.start) for x in r.records)
        busy_t = math.fsum(x.end - x.start for x in r.records)
        idle_e = idle_w * (n_dev * horizon - busy_t)
        assert led.energy_j() == pytest.approx(busy + idle_e, rel=1e-9)
        assert all(s.watts >= 0.0 for s in led.segments)
        # truncated horizon still exact (clipped busy + clipped idle)
        h2 = horizon * 0.5
        led2 = PowerTelemetry.from_result(r, idle_powers=idle_w,
                                          n_devices=n_dev, horizon=h2)
        busy2 = busy_t2 = 0.0
        for x in r.records:
            lo, hi = max(x.start, 0.0), min(x.end, h2)
            if hi > lo:
                busy2 += x.power_w * (hi - lo)
                busy_t2 += hi - lo
        assert led2.energy_j() == pytest.approx(
            busy2 + idle_w * (n_dev * h2 - busy_t2), rel=1e-9)

    _split_cache: dict = {}        # class-level: shared across instances

    def _split_run(self):
        if "run" not in self._split_cache:
            jobs, r, mgr, coord = _preemptive_run(0, 2)
            assert r.preemptions > 0   # the net must cover split intervals
            self._split_cache["run"] = (jobs, r, mgr, coord)
        return self._split_cache["run"]


# ---------------------------------------------------------------------- #
#  Cold-start fuzz: mixed profiled/unseen app set (PR 8)
# ---------------------------------------------------------------------- #
def _mixed_jobs(seed: int, pool_idx: int, quantum: float) -> list[Job]:
    """A stream interleaving the profiled corpus with never-profiled
    variants (new names, divergent latents) the synthesizer must serve."""
    f = _fixture()
    _, _, n_dev = _POOLS[pool_idx]
    rng = np.random.default_rng(seed)
    novel = [dataclasses.replace(
        APPS[i % len(APPS)], name=f"novel-{i}", seed=700 + i,
        stall_frac=float(rng.uniform(0.2, 0.5)),
        core_eff=float(rng.uniform(0.55, 0.85)))
        for i in range(3)]
    # every novel app must arrive, or the synthesizer has nothing to
    # serve: redraw the stream (a fixed seed offset) until all three do
    names = {a.name for a in novel}
    attempt = 0
    while True:
        jobs = list(stream_workload(APPS + novel, f["testbed"], n_jobs=30,
                                    seed=seed + 1000 * attempt,
                                    n_devices=n_dev))
        if names <= {j.app.name for j in jobs}:
            break
        attempt += 1
    return [dataclasses.replace(j, checkpoint_quantum=quantum)
            for j in jobs]


def _cold_run(jobs, pool_idx: int, policy: str, coordinator, preemption):
    from repro.core import ColdStartSynthesizer
    f = _fixture()
    _, pool, n_dev = _POOLS[pool_idx]
    synth = ColdStartSynthesizer()
    r = run_schedule(
        jobs, policy, Testbed(seed=1000),
        predictor=f["predictor"], app_features=f["features"],
        n_devices=n_dev, device_classes=pool,
        power_coordinator=coordinator, preemption=preemption,
        coldstart=synth)
    return r, synth


def _cold_coordinator(cap_kind: str, jobs, pool_idx: int, policy: str):
    """Like _coordinator, but the headroom probe runs with a synthesizer
    attached (the mixed stream is unschedulable without one)."""
    if cap_kind == "none":
        return None
    if cap_kind == "inf":
        return PowerCapCoordinator(math.inf, guard=0.15)
    f = _fixture()
    _, pool, n_dev = _POOLS[pool_idx]
    r0, _ = _cold_run(jobs, pool_idx, policy, None, None)
    if pool is not None:
        led = PowerTelemetry.from_result(r0, pool=pool)
        idle = sum(c.idle_power() for c in pool)
    else:
        idle_w = f["testbed"].idle_power()
        led = PowerTelemetry.from_result(r0, idle_powers=idle_w,
                                         n_devices=n_dev)
        idle = idle_w * n_dev
    cap = idle + 0.6 * max(led.peak_w - idle, 1.0)
    return PowerCapCoordinator(cap, grant_policy="slack-weighted",
                               guard=0.15)


class TestColdStartMixedFuzz:
    """Random pool x policy x cap x preemption configurations on a mixed
    profiled/unseen stream: the engine must admit unknown apps through the
    synthesized tier and keep every structural invariant the profiled-only
    fuzz pins — overlap-free devices, EDF dispatch among admitted jobs,
    and exact energy/work conservation."""

    def _check_structure(self, jobs, r):
        # every job executes; per-job work sums to 1 with one final record
        by_job: dict[int, list] = {}
        for rec in r.records:
            by_job.setdefault(rec.job_id, []).append(rec)
        assert sorted(by_job) == sorted(j.job_id for j in jobs)
        for jid, recs in by_job.items():
            recs.sort(key=lambda x: x.start)
            assert math.fsum(x.work_frac for x in recs) == pytest.approx(
                1.0, abs=1e-9), jid
            assert [x.preempted for x in recs] == \
                [True] * (len(recs) - 1) + [False]
        # energy-conserving: billed energy decomposes exactly
        for rec in r.records:
            assert rec.energy_j == pytest.approx(
                rec.time_s * rec.power_w + rec.overhead_j, rel=1e-12)
        # overlap-free: per-device busy spans never intersect
        by_dev: dict[int, list] = {}
        for rec in r.records:
            by_dev.setdefault(rec.device, []).append((rec.start, rec.end))
        for spans in by_dev.values():
            spans.sort()
            for (_, e1), (s2, _) in zip(spans, spans[1:]):
                assert s2 >= e1 - 1e-9

    def _check_edf(self, jobs, r):
        """EDF-among-admitted: when job b started while job a was already
        pending (arrived, unstarted) with an earlier deadline, the engine
        would have dispatched a first — so no such pair may exist."""
        starts = {rec.job_id: rec.start for rec in r.records
                  if rec.segment == 0}
        by_id = {j.job_id: j for j in jobs}
        order = sorted(starts.items(), key=lambda kv: kv[1])
        for i, (jb, sb) in enumerate(order):
            for ja, sa in order[i + 1:]:
                a, b = by_id[ja], by_id[jb]
                if a.arrival <= sb and sa > sb:
                    assert a.deadline >= b.deadline - 1e-9, (ja, jb)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 50),
           pool_idx=st.integers(0, len(_POOLS) - 1),
           policy=st.sampled_from(list(POLICY_NAMES)))
    def test_uncapped_nonpreemptive_invariants(self, seed, pool_idx,
                                               policy):
        jobs = _mixed_jobs(seed, pool_idx, 0.0)
        r, synth = _cold_run(jobs, pool_idx, policy, None, None)
        assert synth.stats.registered == 3       # unseen apps really served
        assert {rec.name for rec in r.records} >= {
            f"novel-{i}" for i in range(3)}
        self._check_structure(jobs, r)
        self._check_edf(jobs, r)

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 50),
           pool_idx=st.integers(0, len(_POOLS) - 1),
           policy=st.sampled_from(["min-energy", "d-dvfs", "risk-aware"]),
           cap_kind=st.sampled_from(list(_CAPS)),
           preempt=st.sampled_from([False, True]),
           quantum=st.floats(0.05, 1.5))
    def test_capped_preemptive_invariants(self, seed, pool_idx, policy,
                                          cap_kind, preempt, quantum):
        jobs = _mixed_jobs(seed, pool_idx, quantum)
        coord = _cold_coordinator(cap_kind, jobs, pool_idx, policy)
        mgr = PreemptionManager(_ARMED) if preempt else None
        r, synth = _cold_run(jobs, pool_idx, policy, coord, mgr)
        assert synth.stats.registered == 3
        self._check_structure(jobs, r)

    def test_identity_with_trigger_disabled_manager(self):
        """The PR 5 differential net extends to the cold tier: a mixed
        stream through the segmented-but-never-preempting engine is
        bit-identical to the plain engine, synthesizer attached both
        times."""
        jobs = _mixed_jobs(7, 1, 0.2)
        a, _ = _cold_run(jobs, 1, "min-energy", None, None)
        b, _ = _cold_run(jobs, 1, "min-energy", None,
                         PreemptionManager(_OFF))
        _assert_identical(a, b)


# ---------------------------------------------------------------------- #
#  Model-derived apps (PR 10): inert registration + mixed-stream fuzz
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=1)
def _model_fixture():
    """The PR 10 fixture: the paper fixture plus the model-derived suite's
    feature vectors (registered through the dedicated-RNG profiling path,
    so building this perturbs nothing the paper fixture computed)."""
    f = _fixture()
    suite = model_app_suite()
    model_feats = register_model_apps(None, f["testbed"])
    return {**f, "suite": suite,
            "features_all": {**f["features"], **model_feats}}


@functools.lru_cache(maxsize=16)
def _mixed_model_jobs(seed: int, pool_idx: int, quantum: float):
    """Paper stream + diurnal serving mix + background train jobs, merged
    in arrival order with contiguous ids."""
    f = _model_fixture()
    _, pool, n_dev = _POOLS[pool_idx]
    jobs = merge_workloads(
        stream_workload(APPS, f["testbed"], n_jobs=12, seed=seed,
                        n_devices=n_dev),
        serving_workload(f["suite"], f["testbed"], n_jobs=14, seed=seed + 1,
                         pool=pool, n_devices=n_dev),
        training_workload(f["suite"], f["testbed"], n_jobs=6, seed=seed + 2,
                          pool=pool, n_devices=n_dev))
    if quantum:
        jobs = [dataclasses.replace(j, checkpoint_quantum=quantum)
                for j in jobs]
    return jobs


def _model_run(jobs, pool_idx: int, policy: str, coordinator, preemption):
    f = _model_fixture()
    _, pool, n_dev = _POOLS[pool_idx]
    return run_schedule(
        jobs, policy, Testbed(seed=1000),
        predictor=f["predictor"], app_features=f["features_all"],
        n_devices=n_dev, device_classes=pool,
        power_coordinator=coordinator, preemption=preemption)


class TestModelAppRegistrationInert:
    """Satellite: registering the derived suite must be observationally
    inert — a paper-suite-only run is bit-identical whether or not
    `model_apps` features sit in the service (invariant 12)."""

    def test_paper_only_bit_identical_all_policies(self):
        """Exhaustive over all six policies on the mixed-class pool:
        same jobs, same testbed seed, records bit-identical with the
        model-derived features merely registered."""
        f = _model_fixture()
        jobs = _jobs(3, 3, 0.0)
        for policy in POLICY_NAMES:
            a = _run(jobs, 3, policy, None, None)
            b = _model_run(jobs, 3, policy, None, None)
            _assert_identical(a, b)

    def test_paper_only_identical_capped_and_segmented(self):
        """The same inertness through the coordinated + segmented paths
        (binding cap, trigger-disabled manager): grants, deferrals, and
        boundary visits all line up."""
        jobs = _jobs(5, 1, 0.3)
        for cap_kind in ("none", "binding"):
            coord_a = _coordinator(cap_kind, jobs, 1, "min-energy")
            coord_b = _coordinator(cap_kind, jobs, 1, "min-energy")
            a = _run(jobs, 1, "min-energy", coord_a, None)
            b = _model_run(jobs, 1, "min-energy", coord_b,
                           PreemptionManager(_OFF))
            _assert_identical(a, b)

    def test_registration_preserves_rng_and_features(self):
        """Building the model fixture never mutates the paper fixture's
        feature dict or the shared testbed RNG state (the engine's
        determinism backbone)."""
        f0 = _fixture()
        state = f0["testbed"]._rng.bit_generator.state
        fm = _model_fixture()
        assert f0["testbed"]._rng.bit_generator.state == state
        assert set(f0["features"]) < set(fm["features_all"])
        for name in f0["features"]:
            assert fm["features_all"][name] is f0["features"][name]


class TestMixedModelStreamFuzz:
    """Satellite: paper + serving + training job mixes keep every
    structural invariant the profiled-only fuzz pins — uncapped, capped,
    and preemptive — with tier-aware EDF dispatch among admitted jobs."""

    def _check_edf_tiered(self, jobs, r):
        """EDF-among-admitted, generalized to SLA tiers: if job b started
        while a higher-urgency job a (by ``edf_key``: tier priority, then
        deadline) was already pending, the engine would have dispatched a
        first — so no such pair may exist."""
        starts = {rec.job_id: rec.start for rec in r.records
                  if rec.segment == 0}
        by_id = {j.job_id: j for j in jobs}
        order = sorted(starts.items(), key=lambda kv: kv[1])
        for i, (jb, sb) in enumerate(order):
            for ja, sa in order[i + 1:]:
                a, b = by_id[ja], by_id[jb]
                if a.arrival <= sb and sa > sb:
                    ka, kb = edf_key(a), edf_key(b)
                    assert (ka[0] > kb[0]
                            or (ka[0] == kb[0] and ka[1] >= kb[1] - 1e-9)), \
                        (ja, jb)

    def test_mixed_stream_is_not_vacuous(self):
        """The merged stream really schedules all three populations: at
        least one decode segment, one train step, multiple architectures,
        and at least one paper app are dispatched."""
        jobs = _mixed_model_jobs(0, 3, 0.0)
        r = _model_run(jobs, 3, "min-energy", None, None)
        names = {rec.name for rec in r.records}
        assert any(n.endswith(":decode") for n in names)
        assert any(n.endswith(":train_step") for n in names)
        assert len({n.split(":")[0] for n in names if ":" in n}) >= 2
        assert names & {a.name for a in APPS}

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 50),
           pool_idx=st.integers(0, len(_POOLS) - 1),
           policy=st.sampled_from(list(POLICY_NAMES)))
    def test_uncapped_nonpreemptive_invariants(self, seed, pool_idx,
                                               policy):
        jobs = _mixed_model_jobs(seed, pool_idx, 0.0)
        r = _model_run(jobs, pool_idx, policy, None, None)
        TestColdStartMixedFuzz._check_structure(self, jobs, r)
        self._check_edf_tiered(jobs, r)

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 50),
           pool_idx=st.integers(0, len(_POOLS) - 1),
           policy=st.sampled_from(["min-energy", "d-dvfs", "risk-aware"]),
           cap_kind=st.sampled_from(list(_CAPS)),
           preempt=st.sampled_from([False, True]),
           quantum=st.floats(0.05, 1.5))
    def test_capped_preemptive_invariants(self, seed, pool_idx, policy,
                                          cap_kind, preempt, quantum):
        jobs = _mixed_model_jobs(seed, pool_idx, quantum)
        if cap_kind == "none":
            coord = None
        elif cap_kind == "inf":
            coord = PowerCapCoordinator(math.inf, guard=0.15)
        else:
            f = _model_fixture()
            _, pool, n_dev = _POOLS[pool_idx]
            r0 = _model_run(jobs, pool_idx, policy, None, None)
            if pool is not None:
                led = PowerTelemetry.from_result(r0, pool=pool)
                idle = sum(c.idle_power() for c in pool)
            else:
                idle_w = f["testbed"].idle_power()
                led = PowerTelemetry.from_result(r0, idle_powers=idle_w,
                                                 n_devices=n_dev)
                idle = idle_w * n_dev
            coord = PowerCapCoordinator(
                idle + 0.6 * max(led.peak_w - idle, 1.0),
                grant_policy="slack-weighted", guard=0.15)
        mgr = PreemptionManager(_ARMED) if preempt else None
        r = _model_run(jobs, pool_idx, policy, coord, mgr)
        TestColdStartMixedFuzz._check_structure(self, jobs, r)

    def test_segmented_never_preempted_identity_on_mixed_stream(self):
        """The PR 5 differential identity extends to the model-derived
        mix: trigger-disabled segmentation reproduces the plain engine
        bit-for-bit on a paper+serving+training stream."""
        jobs = _mixed_model_jobs(7, 3, 0.2)
        a = _model_run(jobs, 3, "min-energy", None, None)
        mgr = PreemptionManager(_OFF)
        b = _model_run(jobs, 3, "min-energy", None, mgr)
        _assert_identical(a, b)
        assert mgr.stats.preemptions == 0


# ---------------------------------------------------------------------- #
#  BudgetManager.snapshot/restore: rollbacks compose under interleavings
# ---------------------------------------------------------------------- #
class TestBudgetRollback:
    def _tmin(self):
        tb = _fixture()["testbed"]
        return {a.name: tb.true_time(a, V5E_DVFS.max_clock) for a in APPS}

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 100), defer_p=st.floats(0.1, 0.6))
    def test_queue_aware_rollbacks_compose(self, seed, defer_p):
        """Random admit / dispatch / deferral(snapshot-pop-apply-restore)
        interleavings — including remnant-style re-admissions right after
        a rollback: the manager's budget always equals the brute-force
        recomputation over the jobs *actually* queued, i.e. every
        rollback restored exactly the popped decision and nothing else,
        no matter how many compose."""
        rng = np.random.default_rng(seed)
        tmin = self._tmin()
        tb = _fixture()["testbed"]
        jobs = list(stream_workload(APPS, tb, n_jobs=30, seed=seed))
        mgr = QueueAwareBudget(lambda j: tmin[j.name])
        mgr.reset()
        queued: list[tuple[float, int, Job]] = []
        counter = 0

        def check(job):
            start = float(rng.uniform(0, 100))
            b0 = float(rng.uniform(10, 200))
            got = mgr.apply(job, start, b0)
            want, cum = b0, 0.0
            for dl_j, _, job_j in sorted(queued):
                cum += tmin[job_j.name]
                want = min(want, dl_j - start - cum)
            assert got == pytest.approx(want, abs=1e-12)

        for j in jobs:
            mgr.on_admit(j)
            queued.append((j.deadline, counter, j))
            counter += 1
            r = rng.random()
            if queued and r < defer_p:
                # deferral: snapshot → pop → apply → restore (the capped
                # engine's rollback path), sometimes twice in a row —
                # with admissions continuing between episodes, exactly
                # the remnant-re-admission interleaving the preemptive
                # loop produces
                for _ in range(1 + int(rng.random() < 0.3)):
                    k = int(rng.integers(len(queued)))
                    _, _, victim = queued[k]
                    snap = mgr.snapshot()
                    mgr.on_pop(victim)
                    mgr.apply(victim, float(rng.uniform(0, 50)), 100.0)
                    mgr.restore(snap)
                    check(victim)
            elif queued and r < defer_p + 0.3:
                k = int(rng.integers(len(queued)))
                _, _, popped = queued.pop(k)
                mgr.on_pop(popped)          # a real dispatch: no rollback
            check(j)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 100))
    def test_virtual_pacing_rollbacks_compose(self, seed):
        rng = np.random.default_rng(seed)
        tb = _fixture()["testbed"]
        jobs = list(stream_workload(APPS, tb, n_jobs=20, seed=seed))
        t_dc = {a.name: tb.true_time(a, V5E_DVFS.default_clock)
                for a in APPS}
        mgr = VirtualPacingBudget(lambda j: t_dc[j.name])
        mgr.reset()
        shadow = VirtualPacingBudget(lambda j: t_dc[j.name])
        shadow.reset()
        for j in jobs:
            start = float(rng.uniform(0, 200))
            if rng.random() < 0.5:
                # deferred decision (possibly nested twice): net no-op
                for _ in range(1 + int(rng.random() < 0.4)):
                    snap = mgr.snapshot()
                    mgr.apply(j, start, 100.0)
                    mgr.restore(snap)
            got = mgr.apply(j, start, 100.0)
            want = shadow.apply(j, start, 100.0)
            assert got == pytest.approx(want, abs=1e-12)
            assert mgr.snapshot() == shadow.snapshot()


# ---------------------------------------------------------------------- #
#  Rescue-decision units (PreemptionManager.decide, branch by branch)
# ---------------------------------------------------------------------- #
class TestRescueDecision:
    """Drive decide() against a fabricated engine/segment so every
    verdict branch — including the watt-limited cap-rescue labeling the
    integration streams rarely reach — is pinned directly."""

    def _setup(self, *, committed_T=20.0, fast_T=2.0, fast_P=200.0,
               grant=None, potential=math.inf, deadline=10.0,
               remaining=0.5):
        import types
        clocks = (V5E_DVFS.min_clock, V5E_DVFS.max_clock)
        tab = ClockTable(clocks=clocks,
                         P=np.array([50.0, fast_P]),
                         T=np.array([committed_T, fast_T]))
        coord = types.SimpleNamespace(
            guard=0.0, potential_w=lambda dev: potential)
        engine = types.SimpleNamespace(
            _table_for=lambda job, cls: tab,
            _t_min_est=lambda job, cls: None,
            policy=MinEnergy(V5E_DVFS),
            power_coordinator=coord if grant is not None else None,
            n_devices=1)
        job = Job(app=APPS[0], arrival=0.0, deadline=deadline, job_id=0,
                  checkpoint_quantum=0.5)
        seg = types.SimpleNamespace(
            job=job, dev=0, device_class=None, class_key=None,
            clock=clocks[0], grant=grant, done=False, end=100.0,
            remaining_at=lambda t: remaining)
        return engine, seg

    def test_self_rescue_fires_on_predicted_miss(self):
        engine, seg = self._setup()
        mgr = PreemptionManager(PreemptionConfig())
        # committed: 0.5 x 20 = 10s remaining from t=1 -> misses t=10;
        # the fast clock (0.5 x 2 + overheads) saves it
        assert mgr.decide(engine, seg, 1.0, [], {}) == "self-rescue"
        assert mgr.stats.self_rescues == 1

    def test_cap_rescue_labels_watt_limited_rescue(self):
        # same geometry, but the running grant (60 W) blocks the 200 W
        # fast clock while the coordinator's reclaim bound covers it:
        # the rescue is real and must be labeled cap-rescue
        engine, seg = self._setup(grant=60.0, potential=500.0)
        mgr = PreemptionManager(PreemptionConfig())
        assert mgr.decide(engine, seg, 1.0, [], {}) == "cap-rescue"
        assert mgr.stats.cap_rescues == 1
        assert mgr.stats.self_rescues == 0

    def test_rescue_declined_when_no_watts_reclaimable(self):
        # the fast clock exceeds even the reclaim bound: preempting buys
        # nothing, the boundary must decline
        engine, seg = self._setup(grant=60.0, potential=100.0)
        mgr = PreemptionManager(PreemptionConfig())
        assert mgr.decide(engine, seg, 1.0, [], {}) is None
        assert mgr.stats.declined == 1

    def test_rescue_declined_when_doomed(self):
        # even the fastest clock cannot make the deadline: decline (the
        # sprint-on-miss burn stays where it is, no checkpoint waste)
        engine, seg = self._setup(fast_T=30.0)
        mgr = PreemptionManager(PreemptionConfig())
        assert mgr.decide(engine, seg, 1.0, [], {}) is None

    def test_rescue_declined_when_healthy(self):
        engine, seg = self._setup(committed_T=4.0, deadline=50.0)
        mgr = PreemptionManager(PreemptionConfig())
        assert mgr.decide(engine, seg, 1.0, [], {}) is None

    def test_nearly_done_jobs_never_preempted(self):
        engine, seg = self._setup(remaining=0.01)
        mgr = PreemptionManager(PreemptionConfig())
        assert mgr.decide(engine, seg, 1.0, [], {}) is None
        assert mgr.stats.checks == 0       # below min_remnant_frac

    def test_max_preemptions_bounds_remnant_storms(self):
        engine, seg = self._setup()
        seg.job = dataclasses.replace(seg.job, segment=8)
        mgr = PreemptionManager(PreemptionConfig(max_preemptions=8))
        assert mgr.decide(engine, seg, 1.0, [], {}) is None


# ---------------------------------------------------------------------- #
#  Policy-level remnant units
# ---------------------------------------------------------------------- #
class TestResumeSelection:
    def _table(self):
        clocks = tuple(V5E_DVFS.clock_list())
        T = np.linspace(40.0, 8.0, len(clocks))
        P = np.linspace(60.0, 220.0, len(clocks))
        return ClockTable(clocks=clocks, P=P, T=T)

    def test_select_resume_scales_remaining_work(self):
        pol = MinEnergy(V5E_DVFS)
        tab = self._table()
        job = Job(app=APPS[0], arrival=0.0, deadline=100.0, job_id=0)
        # whole job: nothing feasible within 10 s except the fast end
        whole = pol.select_clock(job, 10.0, tab)
        # half the work + 0.5 s restore: slower, cheaper clocks open up
        half = pol.select_resume(job, 10.0, tab, work_frac=0.5,
                                 overhead_s=0.5)
        assert whole.feasible and half.feasible
        assert half.time <= whole.time     # scaled table times
        i_whole = tab.clocks.index(whole.clock)
        i_half = tab.clocks.index(half.clock)
        assert i_half <= i_whole           # never a faster clock needed
        # the scaled prediction is exactly work_frac * T + overhead
        assert half.time == pytest.approx(
            0.5 * tab.T[i_half] + 0.5, rel=1e-12)

    def test_rescue_trigger_margins(self):
        pol = MinEnergy(V5E_DVFS)
        assert pol.rescue_trigger(10.0, 15.0, 6.0)          # 16 > 15
        assert not pol.rescue_trigger(10.0, 15.0, 4.0)      # 14 < 15
        # margin inflates the estimate: 4.8 -> 14.8 still fine, 5 x 1.2
        # -> 16 trips
        assert not pol.rescue_trigger(10.0, 15.0, 4.0, margin=0.2)
        assert pol.rescue_trigger(10.0, 15.0, 5.0, margin=0.2)

    def test_select_resume_whole_job_is_plain_selection(self):
        pol = MinEnergy(V5E_DVFS)
        tab = self._table()
        job = Job(app=APPS[0], arrival=0.0, deadline=100.0, job_id=0)
        a = pol.select_clock(job, 30.0, tab)
        b = pol.select_resume(job, 30.0, tab, work_frac=1.0,
                              overhead_s=0.0)
        assert a == b

    def test_select_resume_matches_engine_remnant_lens(self):
        """select_resume (the policy-level API) and the engine's actual
        resume path (remnant_view -> select_for_class) must agree for
        any (work_frac, overhead): both delegate to ClockTable.remnant,
        and this pins that they can never drift apart."""
        pol = MinEnergy(V5E_DVFS)
        tab = self._table()
        mgr = PreemptionManager(PreemptionConfig(restore_s=0.7))
        for wf in (0.15, 0.5, 0.9):
            job = Job(app=APPS[0], arrival=0.0, deadline=100.0, job_id=0,
                      work_frac=wf, segment=1)
            via_api = pol.select_resume(job, 12.0, tab, work_frac=wf,
                                        overhead_s=0.7)
            via_engine = pol.select_for_class(
                job, 12.0, mgr.remnant_view(tab, job))
            assert via_api == via_engine
