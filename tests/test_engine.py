"""Tests for the service-oriented scheduling stack: PredictionService cache
correctness, policy/budget-manager equivalence with the legacy monolith
(bit-for-bit, every policy, multiple seeds), and EventEngine streaming +
multi-device behavior."""
import functools
import itertools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # not installed in this container — deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs.paper_suite import PAPER_APPS
from repro.core import (
    CorrelationIndex, EnergyTimePredictor, EngineHooks, EventEngine, Job,
    PredictionService, PredictorConfig, Testbed, V5E_CLASS, V5E_DVFS,
    V5LITE_CLASS, V5P_CLASS, build_dataset, heterogeneous_workload,
    make_device_pool, make_workload, profile_features, run_schedule,
    stream_workload,
)
from repro.core.features import clock_features
from repro.core.gbdt import GBDTParams
from repro.core.policies import (POLICIES, POLICY_NAMES, MinEnergy,
                                 QueueAwareBudget, resolve_policy)
from repro.core.scheduler import POLICIES as POLICY_TUPLE, legacy_run_schedule

APPS = list(PAPER_APPS)[:8]   # subset keeps the fit fast; behavior-identical
SMALL = PredictorConfig(
    gbdt=GBDTParams(iterations=80, depth=3, learning_rate=0.15,
                    l2_leaf_reg=5.0),
    gbdt_time=GBDTParams(iterations=80, depth=3, learning_rate=0.15,
                         l2_leaf_reg=3.0),
)


@pytest.fixture(scope="module")
def testbed():
    return Testbed(seed=0)


@pytest.fixture(scope="module")
def fitted(testbed):
    X, yp, yt, _ = build_dataset(APPS, testbed, seed=0)
    return EnergyTimePredictor(SMALL).fit(X, yp, yt)


@pytest.fixture(scope="module")
def app_feats(testbed):
    rng = np.random.default_rng(7)
    return {a.name: profile_features(a, testbed, rng=rng) for a in APPS}


def _assert_identical(a, b):
    assert a.policy == b.policy
    assert len(a.records) == len(b.records)
    for ra, rb in zip(a.records, b.records):
        assert ra == rb, (ra, rb)


# ---------------------------------------------------------------------- #
#  Equivalence: new stack == legacy monolith, bit-for-bit
# ---------------------------------------------------------------------- #
class TestEquivalence:
    def test_every_policy_every_seed(self, testbed, fitted, app_feats):
        """All six policies, 3 seeds: identical ExecutionRecord streams."""
        for pol, seed in itertools.product(POLICY_NAMES, range(3)):
            jobs = make_workload(APPS, testbed, seed=seed)
            kw = dict(predictor=fitted, app_features=app_feats)
            a = legacy_run_schedule(jobs, pol, Testbed(seed=100 + seed), **kw)
            b = run_schedule(jobs, pol, Testbed(seed=100 + seed), **kw)
            _assert_identical(a, b)

    def test_budget_manager_ablations(self, testbed, fitted, app_feats):
        """queue_aware / virtual_pacing off-switches match legacy exactly."""
        jobs = make_workload(APPS, testbed, seed=1)
        variants = [
            dict(queue_aware=False, virtual_pacing=False),
            dict(queue_aware=True, virtual_pacing=False),
            dict(queue_aware=False, virtual_pacing=True),
            dict(queue_aware=True, virtual_pacing=True, slack_share=0.6),
        ]
        for kw in variants:
            a = legacy_run_schedule(jobs, "d-dvfs", Testbed(seed=100),
                                    predictor=fitted,
                                    app_features=app_feats, **kw)
            b = run_schedule(jobs, "d-dvfs", Testbed(seed=100),
                             predictor=fitted, app_features=app_feats, **kw)
            _assert_identical(a, b)

    def test_with_correlation_index(self, testbed, fitted, app_feats):
        """Paper §III-D indirection path: correlated features, same records."""
        names = list(app_feats)
        F = np.stack([app_feats[n] for n in names])
        idx = CorrelationIndex(k=4, random_state=0).fit(names, F)
        jobs = make_workload(APPS, testbed, seed=2)
        kw = dict(predictor=fitted, app_features=app_feats, corr_index=idx,
                  corr_features=app_feats)
        a = legacy_run_schedule(jobs, "d-dvfs", Testbed(seed=100), **kw)
        b = run_schedule(jobs, "d-dvfs", Testbed(seed=100), **kw)
        _assert_identical(a, b)

    def test_multi_device(self, testbed, fitted, app_feats):
        for nd in (2, 4):
            jobs = make_workload(APPS, testbed, seed=3)
            kw = dict(predictor=fitted, app_features=app_feats, n_devices=nd)
            a = legacy_run_schedule(jobs, "min-energy", Testbed(seed=100),
                                    **kw)
            b = run_schedule(jobs, "min-energy", Testbed(seed=100), **kw)
            _assert_identical(a, b)

    def test_no_predictor_baselines(self, testbed):
        jobs = make_workload(APPS, testbed, seed=4)
        for pol in ("dc", "mc"):
            a = legacy_run_schedule(jobs, pol, Testbed(seed=100))
            b = run_schedule(jobs, pol, Testbed(seed=100))
            _assert_identical(a, b)

    def test_shared_service_across_runs(self, testbed, fitted, app_feats):
        """A reused service (warm caches) must not change results."""
        service = PredictionService(V5E_DVFS, predictor=fitted,
                                    app_features=app_feats, testbed=testbed)
        for seed in range(2):
            jobs = make_workload(APPS, testbed, seed=seed)
            a = legacy_run_schedule(jobs, "min-energy", Testbed(seed=100),
                                    predictor=fitted, app_features=app_feats)
            b = run_schedule(jobs, "min-energy", Testbed(seed=100),
                             service=service)
            _assert_identical(a, b)
        # warm reuse: one table build per distinct app across both runs
        assert service.stats.table_builds <= len(APPS)
        assert service.stats.table_hits > 0

    def test_feedback_disabled_still_identical(self, testbed, fitted,
                                               app_feats):
        """PR 2 frozen-path guarantee: a service with an attached (but
        observation-free) corrector AND a disabled OnlineAdapter feedback
        sink must reproduce the legacy monolith bit-for-bit."""
        from repro.core import ObservationStore, OnlineAdapter, RLSCorrector
        jobs = make_workload(APPS, testbed, seed=5)
        kw = dict(predictor=fitted, app_features=app_feats)
        a = legacy_run_schedule(jobs, "min-energy", Testbed(seed=100), **kw)

        service = PredictionService(V5E_DVFS, predictor=fitted,
                                    app_features=app_feats, testbed=testbed)
        service.attach_corrector(RLSCorrector(ObservationStore()))
        b = run_schedule(jobs, "min-energy", Testbed(seed=100),
                         service=service)
        _assert_identical(a, b)

        service2 = PredictionService(V5E_DVFS, predictor=fitted,
                                     app_features=app_feats, testbed=testbed)
        adapter = OnlineAdapter(service2, enabled=False)
        c = run_schedule(jobs, "min-energy", Testbed(seed=100),
                         service=service2, feedback=adapter)
        _assert_identical(a, c)
        assert adapter.n_observed == 0


# ---------------------------------------------------------------------- #
#  PredictionService
# ---------------------------------------------------------------------- #
class TestPredictionService:
    def _service(self, fitted, app_feats, testbed=None, **kw):
        return PredictionService(V5E_DVFS, predictor=fitted,
                                 app_features=app_feats, testbed=testbed,
                                 **kw)

    def test_table_matches_direct_predictor(self, fitted, app_feats):
        svc = self._service(fitted, app_feats)
        name = APPS[0].name
        tab = svc.table(name)
        X = np.stack([
            np.concatenate([app_feats[name], clock_features(c, V5E_DVFS)])
            for c in V5E_DVFS.clock_list()
        ])
        np.testing.assert_array_equal(tab.P, fitted.predict_power(X))
        np.testing.assert_array_equal(tab.T, fitted.predict_time(X))
        assert len(tab) == len(V5E_DVFS.clock_list())

    def test_one_build_per_app(self, fitted, app_feats):
        svc = self._service(fitted, app_feats)
        for _ in range(5):
            for a in APPS:
                svc.table(a.name)
        assert svc.stats.table_builds == len(APPS)
        assert svc.stats.table_hits == 4 * len(APPS)
        # cached tables are the same object — no recompute, no copy
        assert svc.table(APPS[0].name) is svc.table(APPS[0].name)

    def test_point_predictions_match_direct(self, fitted, app_feats):
        svc = self._service(fitted, app_feats)
        name = APPS[1].name
        for fn, clock in ((svc.t_min, V5E_DVFS.max_clock),
                          (svc.t_dc, V5E_DVFS.default_clock)):
            x = np.concatenate([app_feats[name],
                                clock_features(clock, V5E_DVFS)])
            assert fn(name) == float(fitted.predict_time(x[None])[0])
            fn(name)   # second call: cached
        assert svc.stats.point_predictions == 2

    def test_truth_table_matches_testbed(self, fitted, app_feats, testbed):
        svc = self._service(fitted, app_feats, testbed=testbed)
        app = APPS[2]
        tab = svc.truth_table(app)
        assert tab.source == "truth"
        for i, c in enumerate(tab.clocks):
            assert tab.T[i] == testbed.true_time(app, c)
            assert tab.P[i] == testbed.true_power(app, c)
        svc.truth_table(app)
        assert svc.stats.truth_builds == 1 and svc.stats.truth_hits == 1

    def test_truth_without_testbed_raises(self, fitted, app_feats):
        svc = self._service(fitted, app_feats, testbed=None)
        with pytest.raises(ValueError, match="testbed"):
            svc.truth_table(APPS[0])

    def test_correlated_apps_share_tables(self, fitted, app_feats):
        names = list(app_feats)
        F = np.stack([app_feats[n] for n in names])
        idx = CorrelationIndex(k=2, random_state=0).fit(names, F)
        svc = PredictionService(V5E_DVFS, predictor=fitted,
                                app_features=app_feats, corr_index=idx,
                                corr_features=app_feats)
        for n in names:
            svc.table(n)
        # every table key is a correlate; distinct correlates ≤ distinct apps
        assert svc.stats.table_builds <= len(names)
        for n in names:
            key, feats = svc.resolve(n)
            assert key[0] == "corr"
            np.testing.assert_array_equal(feats, app_feats[key[1]])

    def test_kernel_routing_matches_numpy(self, fitted, app_feats):
        """Forced Pallas path (interpret on CPU) == numpy reference: the
        kernel picks the leaves, the host sums them as numpy does."""
        svc_np = self._service(fitted, app_feats, use_kernel=False)
        svc_k = self._service(fitted, app_feats, use_kernel=True)
        name = APPS[0].name
        t_np, t_k = svc_np.table(name), svc_k.table(name)
        assert svc_k.stats.kernel_batches == 2   # power + time
        np.testing.assert_array_equal(t_k.P, t_np.P)
        np.testing.assert_array_equal(t_k.T, t_np.T)

    def test_kernel_prefetch_matches_numpy_predictor_size(self, testbed,
                                                          app_feats):
        """The predictor's default ensembles (400 depth-4 trees each) on a
        prefetch batch of every app's ladder: kernel tables equal the
        numpy tables bit-for-bit."""
        X, yp, yt, _ = build_dataset(APPS, testbed, seed=0)
        full = EnergyTimePredictor(PredictorConfig()).fit(X, yp, yt)
        assert full.power.gbdt.feats.shape == (400, 4)
        svc_np = self._service(full, app_feats, use_kernel=False)
        svc_k = self._service(full, app_feats, use_kernel=True)
        names = [a.name for a in APPS]
        svc_np.prefetch_tables(names)
        svc_k.prefetch_tables(names)
        assert svc_k.stats.kernel_batches == 2
        for name in names:
            np.testing.assert_array_equal(svc_k.table(name).P,
                                          svc_np.table(name).P)
            np.testing.assert_array_equal(svc_k.table(name).T,
                                          svc_np.table(name).T)

    def test_unknown_app_error_carries_suggestion(self, fitted, app_feats):
        """PR 8 small fix: unknown apps raise a typed UnknownAppError
        (KeyError-compatible) naming the nearest profiled app."""
        from repro.core import UnknownAppError
        svc = self._service(fitted, app_feats)
        with pytest.raises(UnknownAppError,
                           match=r"unknown app 'GEM'.*no cold-start "
                                 r"synthesizer.*nearest profiled app: "
                                 r"'GEMM'") as exc:
            svc.table("GEM")
        assert isinstance(exc.value, KeyError)   # back-compat catch sites
        assert exc.value.name == "GEM"
        assert exc.value.suggestion == "GEMM"
        # point predictions raise the same typed error
        with pytest.raises(UnknownAppError):
            svc.t_min("GEM")

    def test_unknown_app_error_with_empty_corpus(self, fitted):
        from repro.core import UnknownAppError
        svc = PredictionService(V5E_DVFS, predictor=fitted, app_features={})
        with pytest.raises(UnknownAppError,
                           match="no profiled apps at all") as exc:
            svc.resolve("anything")
        assert exc.value.suggestion is None


# ---------------------------------------------------------------------- #
#  EventEngine
# ---------------------------------------------------------------------- #
class TestEventEngine:
    def test_streaming_generator_matches_list(self, testbed, fitted,
                                              app_feats):
        """The engine consumes a generator lazily; results match the same
        jobs materialized up front."""
        def jobs_stream():
            return stream_workload(APPS, testbed, n_jobs=60, seed=5,
                                   n_devices=2)

        materialized = list(jobs_stream())
        kw = dict(predictor=fitted, app_features=app_feats, n_devices=2)
        a = run_schedule(materialized, "min-energy", Testbed(seed=100), **kw)
        b = run_schedule(jobs_stream(), "min-energy", Testbed(seed=100), **kw)
        _assert_identical(a, b)
        assert len(a.records) == 60

    def test_out_of_order_stream_rejected(self, testbed):
        jobs = list(stream_workload(APPS, testbed, n_jobs=5, seed=0))
        jobs[2], jobs[4] = jobs[4], jobs[2]
        with pytest.raises(ValueError, match="out of order"):
            run_schedule(iter(jobs), "dc", Testbed(seed=0))

    def test_multi_device_edf_dispatch(self, testbed, fitted, app_feats):
        """8 devices: all jobs run once, per-device spans never overlap, EDF
        respected among simultaneously-queued jobs, per-device clock state
        tracked."""
        jobs = list(stream_workload(APPS, testbed, n_jobs=120, seed=6,
                                    n_devices=8))
        service = PredictionService(V5E_DVFS, predictor=fitted,
                                    app_features=app_feats, testbed=testbed)
        engine = EventEngine(testbed, MinEnergy(V5E_DVFS), service=service,
                             n_devices=8, seed=100)
        r = engine.run(jobs)
        assert sorted(x.job_id for x in r.records) == sorted(
            j.job_id for j in jobs)
        by_dev = {}
        for x in r.records:
            by_dev.setdefault(x.device, []).append(x)
        assert len(by_dev) > 4      # the fleet actually spreads out
        for recs in by_dev.values():
            spans = sorted((x.start, x.end) for x in recs)
            for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
                assert s2 >= e1 - 1e-9
        # EDF among queued jobs (same check as the legacy suite)
        recs = sorted(r.records, key=lambda x: x.start)
        for dev_recs in by_dev.values():
            dev_recs.sort(key=lambda x: x.start)
            for a, b in zip(dev_recs, dev_recs[1:]):
                if b.arrival <= a.start:
                    assert a.deadline <= b.deadline + 1e-9
        assert set(engine.device_clocks) == set(range(8))
        assert all(c is not None for c in engine.device_clocks.values())

    def test_hooks_fire_per_event(self, testbed, fitted, app_feats):
        jobs = make_workload(APPS, testbed, seed=0)
        events = {"admit": 0, "dispatch": 0, "complete": 0}
        hooks = EngineHooks(
            on_admit=lambda j, t: events.__setitem__(
                "admit", events["admit"] + 1),
            on_dispatch=lambda j, d, c, s: events.__setitem__(
                "dispatch", events["dispatch"] + 1),
            on_complete=lambda r: events.__setitem__(
                "complete", events["complete"] + 1),
        )
        r = run_schedule(jobs, "min-energy", Testbed(seed=100),
                         predictor=fitted, app_features=app_feats,
                         hooks=hooks)
        n = len(r.records)
        assert events == {"admit": n, "dispatch": n, "complete": n}

    def test_unknown_policy_raises(self, testbed):
        with pytest.raises(ValueError, match="unknown policy"):
            run_schedule([], "warp-speed", testbed)

    def test_predictive_policy_needs_predictor(self, testbed):
        with pytest.raises(ValueError, match="needs a fitted predictor"):
            run_schedule([], "d-dvfs", testbed)

    def test_registry_matches_scheduler_tuple(self):
        assert POLICY_TUPLE == POLICY_NAMES == tuple(POLICIES)
        for name in POLICY_NAMES:
            assert resolve_policy(name, V5E_DVFS).name == name


# ---------------------------------------------------------------------- #
#  Budget managers
# ---------------------------------------------------------------------- #
class TestQueueAwareBudget:
    def test_duplicate_job_objects(self, testbed, fitted, app_feats):
        """The same Job object admitted twice (replayed workload) must not
        corrupt the incremental EDF list — results still match legacy."""
        jobs = make_workload(APPS[:4], testbed, seed=0)
        doubled = jobs + jobs              # same objects, twice
        kw = dict(predictor=fitted, app_features=app_feats)
        a = legacy_run_schedule(doubled, "d-dvfs", Testbed(seed=100), **kw)
        b = run_schedule(doubled, "d-dvfs", Testbed(seed=100), **kw)
        _assert_identical(a, b)

    def test_incremental_matches_bruteforce(self, testbed):
        """Random admit/pop interleavings: the incremental EDF list computes
        the same cap as re-sorting the queue (the legacy algorithm)."""
        rng = np.random.default_rng(0)
        jobs = list(stream_workload(APPS, testbed, n_jobs=40, seed=7))
        tmin = {j.name: testbed.true_time(j.app, V5E_DVFS.max_clock)
                for j in jobs}
        mgr = QueueAwareBudget(lambda j: tmin[j.name])
        mgr.reset()
        queued, counter = [], 0
        for j in jobs:
            mgr.on_admit(j)
            queued.append((j.deadline, counter, j))
            counter += 1
            if queued and rng.random() < 0.4:
                k = int(rng.integers(len(queued)))
                dl, c, popped = queued.pop(k)
                mgr.on_pop(popped)
            start = float(rng.uniform(0, 100))
            budget0 = float(rng.uniform(10, 200))
            got = mgr.apply(j, start, budget0)
            want, cum = budget0, 0.0
            for dl_j, _, job_j in sorted(queued):
                cum += tmin[job_j.name]
                want = min(want, dl_j - start - cum)
            assert got == pytest.approx(want, abs=1e-12)


# ---------------------------------------------------------------------- #
#  Heterogeneous pools
# ---------------------------------------------------------------------- #
class TestHeterogeneousPool:
    def test_uniform_class_pool_bit_identical(self, testbed, fitted,
                                              app_feats):
        """The tentpole safety rail: an explicit pool of one device class
        (the baseline chip) reproduces the classless engine's records
        bit-identically — every policy, every field that carries
        behavior."""
        pool = [V5E_CLASS] * 3
        kw = dict(predictor=fitted, app_features=app_feats)
        for pol in POLICY_NAMES:
            jobs = make_workload(APPS, testbed, seed=6)
            a = run_schedule(jobs, pol, Testbed(seed=100), n_devices=3, **kw)
            b = run_schedule(jobs, pol, Testbed(seed=100),
                             device_classes=pool, **kw)
            _assert_identical(a, b)
            assert all(r.device_class == "v5e" for r in b.records)
            assert all(r.device_class is None for r in a.records)

    def test_uniform_single_device_pool_matches_legacy(self, testbed, fitted,
                                                       app_feats):
        """One-device explicit pool: budget managers (queue-aware +
        virtual pacing) stay active and anchored on the pool's class —
        records must still match the legacy monolith bit-for-bit."""
        kw = dict(predictor=fitted, app_features=app_feats)
        for pol in ("d-dvfs", "oracle"):
            jobs = make_workload(APPS, testbed, seed=7)
            a = legacy_run_schedule(jobs, pol, Testbed(seed=100), **kw)
            b = run_schedule(jobs, pol, Testbed(seed=100),
                             device_classes=[V5E_CLASS], **kw)
            _assert_identical(a, b)

    def test_mixed_pool_uses_every_class(self, testbed, fitted, app_feats):
        pool = make_device_pool((V5P_CLASS, 1), (V5E_CLASS, 2),
                                (V5LITE_CLASS, 1))
        jobs = list(heterogeneous_workload(APPS, testbed, pool, n_jobs=80,
                                           seed=0))
        r = run_schedule(jobs, "min-energy", Testbed(seed=100),
                         predictor=fitted, app_features=app_feats,
                         device_classes=pool)
        assert sorted(x.job_id for x in r.records) == sorted(
            j.job_id for j in jobs)
        assert {x.device_class for x in r.records} == {"v5e", "v5p",
                                                       "v5lite"}
        # the selected clock always belongs to the chosen class's ladder
        # (or is its sprint clock), never another class's
        for x in r.records:
            dvfs = {"v5e": V5E_CLASS, "v5p": V5P_CLASS,
                    "v5lite": V5LITE_CLASS}[x.device_class].dvfs
            assert (x.clock in dvfs.clock_list()
                    or x.clock == dvfs.max_clock)

    def test_oracle_mixed_beats_uniform_baseline(self, testbed, fitted,
                                                 app_feats):
        """With ground-truth tables, joint placement on the mixed pool must
        not lose energy vs. blindly running the same stream on the
        earliest-free device (dc placement) of the same pool."""
        pool = make_device_pool((V5P_CLASS, 2), (V5E_CLASS, 2),
                                (V5LITE_CLASS, 2))
        jobs = list(heterogeneous_workload(APPS, testbed, pool, n_jobs=80,
                                           seed=1))
        svc = PredictionService(V5E_DVFS, predictor=fitted,
                                app_features=app_feats, testbed=testbed)
        r_orc = run_schedule(jobs, "oracle", Testbed(seed=100), service=svc,
                             device_classes=pool)
        r_dc = run_schedule(jobs, "dc", Testbed(seed=100), service=svc,
                            device_classes=pool)
        assert r_orc.total_energy < r_dc.total_energy

    def test_equal_free_time_tie_break(self, testbed):
        """The free heap orders by (free_time, device_index) with the index
        as the explicit tie-break: at t=0 every device is free, so the
        first EDF job lands on device 0, the next on device 1, … in pool
        construction order — regardless of which classes sit where (device
        objects never enter the heap, so no TypeError on ties either)."""
        for pool in ([V5LITE_CLASS, V5P_CLASS, V5E_CLASS, V5P_CLASS],
                     [V5P_CLASS, V5LITE_CLASS, V5E_CLASS, V5LITE_CLASS]):
            apps = APPS[:4]
            jobs = [  # all arrive at 0 with strictly increasing deadlines
                Job(app=apps[i], arrival=0.0, deadline=1e4 + i, job_id=i)
                for i in range(4)
            ]
            r = run_schedule(jobs, "dc", Testbed(seed=100),
                             device_classes=pool)
            by_deadline = sorted(r.records, key=lambda x: x.deadline)
            assert [x.device for x in by_deadline] == [0, 1, 2, 3]
            assert [x.device_class for x in by_deadline] == [
                c.name for c in pool]

    def test_losing_candidate_keeps_true_free_time(self, testbed):
        """When the queue is empty the decision time is bumped to the next
        arrival; if the popped device then *loses* the joint decision it
        must go back on the heap with its true free time, not the bumped
        one — otherwise a later decision pops (and places work on) the
        wrong device of a class."""
        from repro.core.simulator import AppProfile
        big = AppProfile(name="big", flops=5e14, hbm_bytes=1e12, seed=1)
        tiny = AppProfile(name="tiny", flops=1e10, hbm_bytes=1e8, seed=2)
        pool = [V5LITE_CLASS, V5P_CLASS, V5LITE_CLASS]
        jobs = [   # oracle sends `big` to v5p (dev1), `tiny` to a v5lite
            Job(app=big, arrival=0.0, deadline=40.0, job_id=0),
            Job(app=big, arrival=50.0, deadline=90.0, job_id=1),
            Job(app=tiny, arrival=200.0, deadline=400.0, job_id=2),
        ]
        r = run_schedule(jobs, "oracle", Testbed(seed=100),
                         device_classes=pool)
        by_id = {x.job_id: x for x in r.records}
        assert by_id[0].device_class == by_id[1].device_class == "v5p"
        assert by_id[2].device_class == "v5lite"
        # dev0 was popped (and bumped) for jobs 0 and 1 but lost both joint
        # decisions; it has been free since t=0, so the tie-break hands it
        # job 2 — a corrupted push-back would route job 2 to dev2 instead
        assert by_id[2].device == 0

    def test_infeasible_everywhere_sprints_on_fastest_class(self):
        """When no class has a feasible clock, candidates rank by predicted
        sprint time — the engine should burn the miss on the fastest class,
        not whichever device happened to free first."""
        from repro.core.policies import DeviceCandidate, MinEnergy
        from repro.core.prediction_service import ClockTable
        pol = MinEnergy(V5E_DVFS)
        slow_clocks = tuple(V5LITE_CLASS.dvfs.clock_list())
        fast_clocks = tuple(V5P_CLASS.dvfs.clock_list())
        slow = ClockTable(clocks=slow_clocks,
                          P=np.full(len(slow_clocks), 50.0),
                          T=np.linspace(40.0, 20.0, len(slow_clocks)))
        fast = ClockTable(clocks=fast_clocks,
                          P=np.full(len(fast_clocks), 200.0),
                          T=np.linspace(9.0, 4.0, len(fast_clocks)))
        job = Job(app=APPS[0], arrival=0.0, deadline=1.0, job_id=0)
        cands = [DeviceCandidate(V5LITE_CLASS, 1.0, slow),
                 DeviceCandidate(V5P_CLASS, 1.0, fast)]
        i, sel = pol.select_device_clock(job, cands)
        assert not sel.feasible
        assert i == 1                       # the fast class eats the miss

    def test_conflicting_class_names_rejected(self, fitted, app_feats,
                                              testbed):
        svc = PredictionService(V5E_DVFS, predictor=fitted,
                                app_features=app_feats, testbed=testbed)
        svc.table(APPS[0].name, V5P_CLASS)
        impostor = V5P_CLASS.__class__("v5p", V5LITE_CLASS.dvfs)
        with pytest.raises(ValueError, match="conflicting"):
            svc.table(APPS[0].name, impostor)

    def test_class_keyed_cache_build_once(self, fitted, app_feats, testbed):
        """One table build per (app, device class); the baseline class
        normalizes onto the classless cache entries (same objects)."""
        svc = PredictionService(V5E_DVFS, predictor=fitted,
                                app_features=app_feats, testbed=testbed)
        for _ in range(3):
            for a in APPS:
                svc.table(a.name)
                svc.table(a.name, V5E_CLASS)      # normalizes to None
                svc.table(a.name, V5P_CLASS)
                svc.table(a.name, V5LITE_CLASS)
        assert svc.stats.table_builds == 3 * len(APPS)
        a0 = APPS[0].name
        assert svc.table(a0) is svc.table(a0, V5E_CLASS)
        assert svc.table(a0, V5P_CLASS) is not svc.table(a0)
        assert len(svc.table(a0, V5LITE_CLASS)) == len(
            V5LITE_CLASS.dvfs.clock_list())


# ---------------------------------------------------------------------- #
#  Property-based engine invariants (heterogeneous pools)
# ---------------------------------------------------------------------- #
_PROP_POOLS = (
    (V5E_CLASS, V5E_CLASS, V5E_CLASS),
    (V5P_CLASS, V5E_CLASS, V5LITE_CLASS),
    (V5LITE_CLASS, V5LITE_CLASS, V5P_CLASS, V5E_CLASS),
    (V5P_CLASS, V5P_CLASS, V5LITE_CLASS, V5LITE_CLASS),
)


@functools.lru_cache(maxsize=1)
def _prop_fixture():
    """Module fixtures rebuilt as a plain cached function — property tests
    must not take function-scoped pytest fixtures under real hypothesis."""
    tb = Testbed(seed=0)
    X, yp, yt, _ = build_dataset(APPS, tb, seed=0)
    rng = np.random.default_rng(7)
    return {
        "testbed": tb,
        "predictor": EnergyTimePredictor(SMALL).fit(X, yp, yt),
        "features": {a.name: profile_features(a, tb, rng=rng)
                     for a in APPS},
    }


class TestEngineProperties:
    """Invariants that must hold for every pool composition, policy, and
    seed — the systematic net under the heterogeneity refactor."""

    def _run(self, pool, seed, policy, with_feedback=False):
        f = _prop_fixture()
        jobs = list(heterogeneous_workload(
            APPS, f["testbed"], list(pool), n_jobs=40, seed=seed))
        events: list[tuple[str, float]] = []

        class _Recorder:
            def observe(self, rec):
                events.append(("obs", rec.end))

        hooks = EngineHooks(
            on_dispatch=lambda j, d, c, s: events.append(("dispatch", s)))
        r = run_schedule(
            jobs, policy, Testbed(seed=100 + seed),
            predictor=f["predictor"], app_features=f["features"],
            device_classes=list(pool), hooks=hooks,
            feedback=_Recorder() if with_feedback else None)
        return jobs, r, events

    @settings(max_examples=8, deadline=None)
    @given(pool_idx=st.integers(0, len(_PROP_POOLS) - 1),
           seed=st.integers(0, 30),
           policy=st.sampled_from(["dc", "min-energy"]))
    def test_property_no_overlap_and_starts(self, pool_idx, seed, policy):
        jobs, r, _ = self._run(_PROP_POOLS[pool_idx], seed, policy)
        assert sorted(x.job_id for x in r.records) == sorted(
            j.job_id for j in jobs)
        for x in r.records:                     # start ≥ arrival, always
            assert x.start >= x.arrival - 1e-9
        by_dev: dict[int, list] = {}
        for x in r.records:
            by_dev.setdefault(x.device, []).append((x.start, x.end))
        for spans in by_dev.values():           # no overlap per device
            spans.sort()
            for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
                assert s2 >= e1 - 1e-9

    @settings(max_examples=8, deadline=None)
    @given(pool_idx=st.integers(0, len(_PROP_POOLS) - 1),
           seed=st.integers(0, 30),
           policy=st.sampled_from(["dc", "min-energy"]))
    def test_property_edf_among_admitted(self, pool_idx, seed, policy):
        """If job b had arrived when job a was dispatched and b is
        dispatched strictly later, EDF demands deadline(a) ≤ deadline(b)
        (every job with arrival ≤ a.start is admitted by a's decision)."""
        _, r, _ = self._run(_PROP_POOLS[pool_idx], seed, policy)
        recs = sorted(r.records, key=lambda x: x.start)
        for i, a in enumerate(recs):
            for b in recs[i + 1:]:
                if b.start > a.start + 1e-12 and b.arrival <= a.start:
                    assert a.deadline <= b.deadline + 1e-9

    @settings(max_examples=6, deadline=None)
    @given(pool_idx=st.integers(0, len(_PROP_POOLS) - 1),
           seed=st.integers(0, 30))
    def test_property_feedback_causality(self, pool_idx, seed):
        """No observation is delivered to a decision earlier in simulated
        time: every delivered measurement's end time precedes the next
        dispatch decision's start."""
        _, _, events = self._run(_PROP_POOLS[pool_idx], seed, "min-energy",
                                 with_feedback=True)
        assert any(kind == "obs" for kind, _ in events)
        next_dispatch_start = [None] * len(events)
        upcoming = None
        for i in range(len(events) - 1, -1, -1):
            next_dispatch_start[i] = upcoming
            if events[i][0] == "dispatch":
                upcoming = events[i][1]
        for (kind, t), nxt in zip(events, next_dispatch_start):
            if kind == "obs" and nxt is not None:
                assert t <= nxt + 1e-9
