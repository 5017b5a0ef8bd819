"""Tests for the multi-rack federation subsystem (repro.core.federation):
topology partition exactness, cap-transfer primitives, facility share
splits and hierarchical escalation, single-rack ≡ bare-coordinator
bit-identity across all six policies, the facility-cap-safety fuzz
(granted-ledger peak ≤ cap for every racks × sizes × cap × grant-policy
draw), and straggler quarantine/migration mechanics."""
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
    FACILITY_SHARE_POLICIES, EnergyTimePredictor, FacilityCoordinator,
    FederatedPreemptionManager, GRANT_POLICIES, MigrationCostModel,
    POLICIES, PowerCapCoordinator, PowerTelemetry, PredictorConfig,
    PreemptionConfig, RackTopology, Testbed, V5E_DVFS, build_dataset,
    multi_rack_workload, profile_features, run_schedule,
)
from repro.core.dvfs import ClockPair
from repro.core.gbdt import GBDTParams

APPS = list(PAPER_APPS)[:6]
SMALL = PredictorConfig(
    gbdt=GBDTParams(iterations=60, depth=3, learning_rate=0.15,
                    l2_leaf_reg=5.0),
    gbdt_time=GBDTParams(iterations=60, depth=3, learning_rate=0.15,
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


@functools.lru_cache(maxsize=1)
def _fixture():
    """lru-cached twin of the module fixtures for the hypothesis fuzz —
    the shim's ``given`` wrapper is signature-opaque to pytest, so fuzz
    tests cannot take fixture arguments."""
    tb = Testbed(seed=0)
    X, yp, yt, _ = build_dataset(APPS, tb, seed=0)
    rng = np.random.default_rng(7)
    return {
        "testbed": tb,
        "predictor": EnergyTimePredictor(SMALL).fit(X, yp, yt),
        "features": {a.name: profile_features(a, tb, rng=rng)
                     for a in APPS},
    }


# ---------------------------------------------------------------------- #
#  Topology: racks partition the pool (invariant 3)
# ---------------------------------------------------------------------- #
class TestRackTopology:
    def test_partition_exact(self):
        topo = RackTopology((2, 3, 1))
        assert topo.n_racks == 3
        assert topo.n_devices == 6
        assert topo.offsets == (0, 2, 5)
        seen = []
        for r in range(topo.n_racks):
            seen.extend(topo.devices_of(r))
        # every device on exactly one rack, in global order
        assert seen == list(range(6))
        for d in range(6):
            r = topo.rack_of(d)
            assert d in topo.devices_of(r)
            assert topo.local_of(d) == d - topo.offsets[r]

    def test_out_of_range_raises(self):
        topo = RackTopology((2, 2))
        with pytest.raises(IndexError):
            topo.rack_of(4)
        with pytest.raises(IndexError):
            topo.rack_of(-1)

    def test_bad_sizes_raise(self):
        with pytest.raises(ValueError):
            RackTopology(())
        with pytest.raises(ValueError):
            RackTopology((2, 0))

    @settings(max_examples=20, deadline=None)
    @given(sizes=st.lists(st.integers(1, 7), min_size=1, max_size=5))
    def test_partition_fuzz(self, sizes):
        topo = RackTopology(tuple(sizes))
        owners = [topo.rack_of(d) for d in range(topo.n_devices)]
        # non-decreasing rack ids, each rack owns exactly its size
        assert owners == sorted(owners)
        for r, s in enumerate(sizes):
            assert owners.count(r) == s


# ---------------------------------------------------------------------- #
#  Migration cost model
# ---------------------------------------------------------------------- #
class TestMigrationCostModel:
    def test_zero_bytes_is_overhead_only(self):
        m = MigrationCostModel()
        secs, joules = m.cost(0.0)
        assert secs == pytest.approx(m.overhead_s)
        assert joules == 0.0

    def test_linear_then_clamped(self):
        m = MigrationCostModel(link_gbps=100.0, overhead_s=0.01,
                               joules_per_gb=10.0, max_bytes=8e9)
        s1, j1 = m.cost(1e9)
        assert s1 == pytest.approx(0.01 + 8.0 / 100.0)
        assert j1 == pytest.approx(10.0)
        # hbm traffic far above resident state clamps at max_bytes
        s_cap, j_cap = m.cost(8e9)
        assert m.cost(500e9) == (pytest.approx(s_cap),
                                 pytest.approx(j_cap))

    def test_negative_bytes_clamped_to_zero(self):
        m = MigrationCostModel()
        assert m.cost(-5.0) == (pytest.approx(m.overhead_s), 0.0)


# ---------------------------------------------------------------------- #
#  Cap-transfer primitives on the rack coordinator
# ---------------------------------------------------------------------- #
class TestCapTransfer:
    def _coord(self, cap=300.0, idle=(20.0, 20.0)):
        c = PowerCapCoordinator(cap)
        c.reset(list(idle))
        return c

    def test_release_cap_moves_only_free_headroom(self):
        c = self._coord(cap=300.0)
        got = c.release_cap(100.0)
        assert got == pytest.approx(100.0)
        assert c.cap_w == pytest.approx(200.0)
        # headroom shrank by exactly what was released
        assert c.headroom_w == pytest.approx(200.0 - c.allocated_w)

    def test_release_cap_bounded_by_headroom(self):
        c = self._coord(cap=300.0)
        free = c.headroom_w
        got = c.release_cap(1e9)
        assert got == pytest.approx(free)
        assert c.cap_w == pytest.approx(300.0 - free)
        # nothing left to give
        assert c.release_cap(10.0) == 0.0

    def test_release_cap_infinite_or_nonpositive_noop(self):
        c = PowerCapCoordinator(math.inf)
        c.reset([20.0])
        assert c.release_cap(50.0) == 0.0
        c2 = self._coord()
        assert c2.release_cap(0.0) == 0.0
        assert c2.cap_w == pytest.approx(300.0)

    def test_resize_below_allocations_raises(self):
        c = self._coord(cap=300.0)
        c.commit(0, 120.0, end=5.0, drawn_w=110.0)
        with pytest.raises(ValueError):
            c.resize_cap(c.allocated_w - 1.0)
        # at or above allocations is fine
        c.resize_cap(c.allocated_w)
        assert c.cap_w == pytest.approx(c.allocated_w)

    def test_reclaim_unused_returns_freed_watts(self):
        c = self._coord(cap=400.0)
        c.commit(0, 150.0, end=5.0, drawn_w=100.0)
        # grant 150 but draw 100 → 50 W reclaimable above the measured
        assert c.reclaimable_w == pytest.approx(50.0)
        freed = c.reclaim_unused()
        assert freed == pytest.approx(50.0)
        assert c.reclaimable_w == 0.0


# ---------------------------------------------------------------------- #
#  Facility share splits
# ---------------------------------------------------------------------- #
class TestFacilityShares:
    IDLE = [20.0] * 6

    def _fac(self, cap, sizes, **kw):
        fac = FacilityCoordinator(cap, sizes, **kw)
        fac.reset(self.IDLE[:fac.n_devices])
        return fac

    @pytest.mark.parametrize("share", FACILITY_SHARE_POLICIES)
    def test_split_sums_to_cap(self, share):
        fac = self._fac(500.0, [2, 3, 1], share_policy=share)
        caps = fac.caps()
        assert math.fsum(caps) <= 500.0 + 1e-9
        # every rack got at least its idle floor
        for r, c in enumerate(caps):
            assert c >= 20.0 * fac.topology.rack_sizes[r] - 1e-9

    def test_single_rack_gets_cap_exactly(self):
        cap = 313.7300000001
        fac = self._fac(cap, [4])
        assert fac.caps() == [cap]     # float-exact, no split arithmetic

    def test_infinite_cap_propagates(self):
        fac = self._fac(math.inf, [2, 2])
        assert fac.caps() == [math.inf, math.inf]

    def test_cap_below_idle_floor_raises(self):
        fac = FacilityCoordinator(50.0, [2, 2])
        with pytest.raises(ValueError):
            fac.reset(self.IDLE[:4])   # idle floor is 80 W

    def test_unknown_policies_raise(self):
        with pytest.raises(ValueError):
            FacilityCoordinator(100.0, [2], share_policy="nope")
        with pytest.raises(ValueError):
            FacilityCoordinator(100.0, [2], grant_policy="nope")
        with pytest.raises(ValueError):
            FacilityCoordinator(-1.0, [2])

    def test_pool_size_mismatch_raises(self):
        fac = FacilityCoordinator(500.0, [2, 2])
        with pytest.raises(ValueError):
            fac.reset([20.0] * 3)

    @pytest.mark.parametrize("share", ("demand-weighted", "tier-weighted"))
    def test_rebalance_preserves_cap_sum(self, share):
        fac = self._fac(500.0, [2, 2, 2], share_policy=share)
        # load rack 0 so rebalancing tilts headroom toward it
        fac.commit(0, 100.0, end=10.0, drawn_w=90.0)
        fac.commit(1, 100.0, end=10.0, drawn_w=90.0)
        fac.advance(1.0)
        assert fac.stats.rebalances >= 1
        assert math.fsum(fac.caps()) <= 500.0 + 1e-9
        # a loaded rack's floor (its allocations) is always covered
        for rack in fac.racks:
            assert rack.coord.cap_w >= rack.coord.allocated_w - 1e-9

    def test_static_never_rebalances(self):
        fac = self._fac(500.0, [2, 2, 2], share_policy="static")
        before = fac.caps()
        fac.commit(0, 100.0, end=10.0, drawn_w=90.0)
        fac.advance(1.0)
        assert fac.stats.rebalances == 0
        # grants expire at advance(20) but caps stay the static split
        fac.advance(20.0)
        assert fac.caps() == before


# ---------------------------------------------------------------------- #
#  Hierarchical escalation
# ---------------------------------------------------------------------- #
class TestEscalation:
    def _fac(self, **kw):
        fac = FacilityCoordinator(400.0, [2, 2], share_policy="static",
                                  **kw)
        fac.reset([20.0] * 4)
        return fac

    def test_sibling_cap_moves_on_escalation(self):
        fac = self._fac()
        cap0, cap1 = fac.caps()
        # rack 0 wants more than its whole slice
        need = cap0 + 50.0
        got = fac.escalate(0, need, start=0.0)
        assert got >= need - 1e-9
        assert fac.stats.escalations == 1
        assert fac.stats.rescues == 1
        assert fac.stats.transfers >= 1
        # watts conserved: what rack 0 gained, rack 1 + pool lost
        assert math.fsum(fac.caps()) <= 400.0 + 1e-9
        assert fac.caps()[0] > cap0
        assert fac.caps()[1] < cap1

    def test_escalation_disabled_stays_local(self):
        fac = self._fac(escalation=False)
        cap0 = fac.caps()[0]
        got = fac.escalate(0, cap0 + 50.0, start=0.0)
        assert got <= cap0 + 1e-9
        assert fac.stats.escalations == 0
        assert fac.caps()[0] == cap0

    def test_local_coverage_never_escalates(self):
        fac = self._fac()
        got = fac.escalate(0, 30.0, start=0.0)   # well inside rack 0's cap
        assert got >= 30.0 - 1e-9
        assert fac.stats.escalations == 0

    def test_potential_includes_sibling_spare(self):
        fac = self._fac()
        local_only = self._fac(escalation=False)
        assert fac.potential_w(0) > local_only.potential_w(0)


# ---------------------------------------------------------------------- #
#  Single-rack identity (invariant 2): all six policies
# ---------------------------------------------------------------------- #
class TestSingleRackIdentity:
    @pytest.mark.parametrize("policy", POLICIES)
    def test_bit_identical_to_bare_coordinator(self, policy, testbed,
                                               fitted, app_feats):
        jobs = list(multi_rack_workload(APPS, testbed, n_devices=3,
                                        n_jobs=30, seed=5))
        kw = dict(predictor=fitted, app_features=app_feats, n_devices=3)
        for grant in GRANT_POLICIES:
            fed = FacilityCoordinator(430.0, [3], grant_policy=grant)
            bare = PowerCapCoordinator(430.0, grant_policy=grant)
            r1 = run_schedule(jobs, policy, Testbed(seed=1000),
                              power_coordinator=fed, **kw)
            r2 = run_schedule(jobs, policy, Testbed(seed=1000),
                              power_coordinator=bare, **kw)
            assert len(r1.records) == len(r2.records)
            for a, b in zip(r1.records, r2.records):
                # rack provenance is the *only* allowed difference
                assert a == b, (policy, grant, a, b)
                assert (a.start, a.end, a.energy_j, a.power_grant_w) == \
                    (b.start, b.end, b.energy_j, b.power_grant_w)
                assert a.rack == 0 and b.rack is None
            assert r1.migrations == 0


# ---------------------------------------------------------------------- #
#  Facility cap safety fuzz (invariant 1)
# ---------------------------------------------------------------------- #
class TestFacilityCapSafety:
    @settings(max_examples=8, deadline=None)
    @given(
        sizes=st.lists(st.integers(1, 3), min_size=1, max_size=3),
        cap_frac=st.floats(0.45, 0.9),
        grant_idx=st.integers(0, len(GRANT_POLICIES) - 1),
        share_idx=st.integers(0, len(FACILITY_SHARE_POLICIES) - 1),
        seed=st.integers(0, 10),
    )
    def test_granted_ledger_peak_under_cap(self, sizes, cap_frac,
                                           grant_idx, share_idx, seed):
        f = _fixture()
        testbed, fitted, app_feats = (f["testbed"], f["predictor"],
                                      f["features"])
        n_dev = sum(sizes)
        jobs = list(multi_rack_workload(APPS, testbed, n_devices=n_dev,
                                        n_jobs=24, seed=seed))
        r0 = run_schedule(jobs, "min-energy", Testbed(seed=1000),
                          predictor=fitted, app_features=app_feats,
                          n_devices=n_dev)
        idle_w = testbed.idle_power()
        led0 = PowerTelemetry.from_result(r0, idle_powers=idle_w,
                                          n_devices=n_dev)
        idle = idle_w * n_dev
        cap = idle + cap_frac * max(led0.peak_w - idle, 1.0)
        fac = FacilityCoordinator(
            cap, sizes, grant_policy=GRANT_POLICIES[grant_idx],
            share_policy=FACILITY_SHARE_POLICIES[share_idx])
        r = run_schedule(jobs, "min-energy", Testbed(seed=1000),
                         predictor=fitted, app_features=app_feats,
                         n_devices=n_dev, power_coordinator=fac)
        for view in ("granted", "measured"):
            led = PowerTelemetry.from_result(
                r, idle_powers=idle_w, n_devices=n_dev, view=view)
            assert led.peak_w <= cap * (1 + 1e-9) + 1e-6, \
                (sizes, cap_frac, view)
        # per-rack caps never sum above the facility cap at the end
        assert math.fsum(fac.caps()) <= cap * (1 + 1e-9) + 1e-6
        # no device ran two jobs at once, and racks partition devices
        by_dev: dict[int, list] = {}
        for rec in r.records:
            assert fac.rack_of(rec.device) == rec.rack
            by_dev.setdefault(rec.device, []).append((rec.start, rec.end))
        for spans in by_dev.values():
            spans.sort()
            for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
                assert s2 >= e1 - 1e-9


# ---------------------------------------------------------------------- #
#  Straggler mechanics: boost ladder, quarantine, migration billing
# ---------------------------------------------------------------------- #
class TestFederatedPreemptionUnit:
    def _mgr(self, sizes=(2, 2), **kw):
        kw.setdefault("dvfs", V5E_DVFS)
        return FederatedPreemptionManager(sizes, **kw)

    def test_slowdown_injection(self):
        mgr = self._mgr(device_slowdown={1: 2.5})
        assert mgr.slowdown_of(1) == 2.5
        assert mgr.slowdown_of(0) == 1.0

    def test_mitigate_clock_identity_when_healthy(self):
        mgr = self._mgr()
        clk = V5E_DVFS.default_clock
        # unflagged device: the SAME object comes back (engine keys its
        # recompute on identity)
        assert mgr.mitigate_clock(0, clk, None) is clk

    def test_mitigate_clock_climbs_ladder(self):
        mgr = self._mgr()
        # flag device 1 via observations
        for _ in range(12):
            mgr.note_step(1, observed_s=3.0, predicted_s=1.0)
            mgr.note_step(0, observed_s=1.0, predicted_s=1.0)
        assert 1 in mgr.monitor.flagged
        clk = ClockPair(min(V5E_DVFS.core_scales), 1.0)
        seen = [clk.s_core]
        for _ in range(len(V5E_DVFS.core_scales) + 2):
            nxt = mgr.mitigate_clock(1, clk, None)
            if nxt.s_core == seen[-1]:
                break
            seen.append(nxt.s_core)
        # strictly climbing, reaches the top rung, then pins there
        assert seen == sorted(set(seen))
        assert mgr.monitor.boosts[1].s_core == max(V5E_DVFS.core_scales)
        assert mgr.monitor.should_evict(1)

    def test_foreign_ladder_never_boosted(self):
        mgr = self._mgr()
        for _ in range(12):
            mgr.note_step(1, observed_s=3.0, predicted_s=1.0)
        clk = ClockPair(min(V5E_DVFS.core_scales), 1.0)
        import dataclasses as dc
        foreign = dc.replace(V5E_DVFS,
                             core_scales=(0.5, 1.0))
        assert mgr.mitigate_clock(1, clk, foreign) is clk

    def test_retire_quarantines_but_never_strands(self):
        mgr = self._mgr(sizes=(1, 1))
        assert mgr.retire("rescue-migration", 0) is True
        assert mgr.quarantined == frozenset({0})
        # last in-service device must stay
        assert mgr.retire("rescue-migration", 1) is False
        assert mgr.quarantined == frozenset({0})
        # non-migration reasons never retire
        assert mgr.retire("cap-rescue", 1) is False

    def test_reset_clears_quarantine_and_monitor(self):
        mgr = self._mgr(sizes=(1, 1))
        mgr.retire("rescue-migration", 0)
        for _ in range(12):
            mgr.note_step(1, observed_s=3.0, predicted_s=1.0)
        mgr.reset()
        assert mgr.quarantined == frozenset()
        assert mgr.monitor.flagged == []
        assert mgr.fed.observations == 0

    def test_migration_cost_same_rack_free(self):
        mgr = self._mgr(sizes=(2, 2))
        job = object.__new__(type("J", (), {}))  # placeholder identity
        mgr._prev_dev[id(job)] = 0
        assert mgr.migration_cost(job, 1) == (0.0, 0.0, None)

    def test_migration_cost_cross_rack_billed(self):
        mgr = self._mgr(sizes=(2, 2))

        class _App:
            hbm_bytes = 4e9

        class _Job:
            app = _App()

        job = _Job()
        mgr._prev_dev[id(job)] = 0
        secs, joules, src = mgr.migration_cost(job, 2)
        exp_s, exp_j = mgr.cost_model.cost(4e9)
        assert (secs, joules, src) == (pytest.approx(exp_s),
                                       pytest.approx(exp_j), 0)
        assert mgr.fed.migration_s == pytest.approx(exp_s)
        assert mgr.fed.migration_j == pytest.approx(exp_j)

    def test_unknown_provenance_is_free(self):
        mgr = self._mgr(sizes=(2, 2))
        class _Job:
            pass
        assert mgr.migration_cost(_Job(), 2) == (0.0, 0.0, None)


# ---------------------------------------------------------------------- #
#  Event-driven grant state ≡ a rescanning reference
# ---------------------------------------------------------------------- #
class _RescanRack(PowerCapCoordinator):
    """A rack coordinator that reads its grant state by rescanning it on
    every call — a frozen copy of the arithmetic from before the release
    heap and the cached sums. The event-driven bookkeeping must match it
    bit for bit."""

    @property
    def allocated_w(self):
        return math.fsum(self._alloc)

    def offer(self, dev, job, start, queue=()):
        self.stats.offers += 1
        idle_d = self._idle[dev]
        if not math.isfinite(self.cap_w):
            return math.inf
        head = self.headroom_w
        if self.grant_policy == "uniform":
            return min(max(self.cap_w / len(self._alloc), idle_d),
                       idle_d + head)
        if self.grant_policy == "greedy-edf":
            return idle_d + head
        w0 = self._urgency(job, start, dev)
        n_free_other = sum(1 for d in range(len(self._alloc))
                           if d not in self._active) - 1
        if n_free_other > 0:
            others = sorted((self._urgency(j, start) for _, _, j in queue),
                            reverse=True)[:n_free_other]
        else:
            others = []
        share = w0 / (w0 + math.fsum(others)) if others else 1.0
        uniform_w = min(max(self.cap_w / len(self._alloc), idle_d),
                        idle_d + head)
        return max(idle_d + head * share, uniform_w)

    def advance(self, t):
        done = [dev for dev, (_, end, _, _) in self._active.items()
                if end <= t + 1e-12]
        for dev in done:
            del self._active[dev]
            self._alloc[dev] = self._idle[dev]

    def next_release(self, t):
        ends = [end for _, end, _, _ in self._active.values()
                if end > t + 1e-12]
        return min(ends) if ends else None


class _RescanFacility(FacilityCoordinator):
    """The facility's ``advance`` and ``_rebalance`` as they rescanned
    every rack on every call, over :class:`_RescanRack` racks."""

    def __init__(self, cap_w, rack_sizes, **kw):
        super().__init__(cap_w, rack_sizes, **kw)
        for rack in self.racks:
            c = rack.coord
            rack.coord = _RescanRack(c.cap_w, grant_policy=c.grant_policy,
                                     guard=c.guard, slack_eps=c.slack_eps)

    def advance(self, t):
        for rack in self.racks:
            rack.coord.advance(t)
        multi = self.n_racks > 1
        if multi and self._grant_tiers:
            live = set()
            for rack in self.racks:
                live.update(rack.offset + d
                            for d in rack.coord.active_grants())
            self._grant_tiers = {d: w for d, w in
                                 self._grant_tiers.items() if d in live}
        if (multi and self.share_policy != "static"
                and math.isfinite(self.cap_w)):
            self._rebalance()

    def _rebalance(self):
        floors = [r.coord.allocated_w for r in self.racks]
        dist = self.cap_w - math.fsum(floors)
        if dist < 0.0:
            dist = 0.0
        bw = self.demand_free_weight
        if self.share_policy == "demand-weighted":
            weights = [1.0 + bw * max(
                rack.size - len(rack.coord.active_grants()), 0)
                for rack in self.racks]
        else:  # tier-weighted
            weights = [
                rack.size + bw * math.fsum(
                    self._grant_tiers.get(rack.offset + d, 1.0)
                    for d in rack.coord.active_grants())
                for rack in self.racks]
        total = math.fsum(weights)
        if total <= 0:
            weights = [float(rack.size) for rack in self.racks]
            total = math.fsum(weights)
        caps = [f + dist * w / total for f, w in zip(floors, weights)]
        caps[-1] = max(self.cap_w - math.fsum(caps[:-1]), floors[-1])
        for rack, cap_r in zip(self.racks, caps):
            rack.coord.resize_cap(cap_r)
        self.stats.rebalances += 1


_FACILITY_FIELDS = ("escalations", "rescues", "transfers", "transferred_w",
                    "rebalances")


def _racks_of(c):
    return [r.coord for r in c.racks] if hasattr(c, "racks") else [c]


def _same_state(new, ref, new_recs, ref_recs, t):
    """Every observable of the grant state, bit for bit."""
    assert _racks_of(new) and len(_racks_of(new)) == len(_racks_of(ref))
    for a, b in zip(_racks_of(new), _racks_of(ref)):
        assert a.cap_w == b.cap_w
        assert a.allocated_w == b.allocated_w
        assert a.headroom_w == b.headroom_w
        assert a.active_grants() == b.active_grants()
        assert a.stats == b.stats
    assert new.allocated_w == ref.allocated_w
    assert new.active_grants() == ref.active_grants()
    for q in (t, t + 0.7, t - 0.3):
        assert new.next_release(q) == ref.next_release(q)
    assert ([r.power_grant_w for r in new_recs]
            == [r.power_grant_w for r in ref_recs])
    if hasattr(new, "racks"):
        assert new.caps() == ref.caps()
        for f in _FACILITY_FIELDS:
            assert getattr(new.stats, f) == getattr(ref.stats, f), f


def _drive(new, ref, n_dev, seed, steps=300):
    """A seeded random sequence of every call that touches the grant state
    (commit, advance, truncate, escalate, release_cap, reclaim_unused,
    offer, potential_w), applied to both coordinators, with every
    observable compared after every step. Ends fall on a 0.25 s grid half
    the time, so releases tie."""
    from types import SimpleNamespace

    from repro.core.workload import TIERS

    rng = np.random.default_rng(seed)
    idle = [float(x) for x in rng.uniform(15.0, 60.0, n_dev)]
    new.reset(idle)
    ref.reset(idle)
    tiers = list(TIERS.values())
    new_recs, ref_recs = [], []
    t = 0.0

    def end_after(t0):
        dt = float(rng.uniform(0.05, 4.0))
        return t0 + (round(dt * 4) / 4 or 0.25 if rng.random() < 0.5
                     else dt)

    def job():
        return SimpleNamespace(deadline=t + float(rng.uniform(0.5, 6.0)),
                               tier=tiers[int(rng.integers(len(tiers)))])

    counts: dict[str, int] = {}
    for _ in range(steps):
        op = str(rng.choice(["commit", "commit", "commit", "advance",
                             "advance", "truncate", "escalate",
                             "release_cap", "reclaim", "offer"]))
        counts[op] = counts.get(op, 0) + 1
        dev = int(rng.integers(n_dev))
        if op == "commit":
            req = idle[dev] + float(rng.uniform(0.0, 250.0))
            drawn = req * float(rng.uniform(0.6, 1.1))
            end = end_after(t)
            tier = tiers[int(rng.integers(len(tiers)))].name
            # a quarter of a facility's commits go to the rack directly:
            # the facility still learns of the lease, and its tier weight
            # is the default one
            direct = hasattr(new, "racks") and rng.random() < 0.25
            for c, recs in ((new, new_recs), (ref, ref_recs)):
                rec = SimpleNamespace(power_grant_w=None, tier=tier)
                recs.append(rec)
                if direct:
                    rack = c.racks[c.rack_of(dev)]
                    rack.coord.commit(rack.local(dev), req, end, drawn,
                                      record=rec)
                else:
                    c.commit(dev, req, end, drawn, record=rec)
        elif op == "advance":
            pending = list(new.active_grants().values())
            if pending and rng.random() < 0.3:
                # land on a grant's end, or a hair before it
                end = pending[int(rng.integers(len(pending)))][1]
                t = max(t, end - float(rng.choice([0.0, 1e-13, 1e-9])))
            else:
                t += float(rng.choice([0.0, rng.uniform(0.0, 1.5)]))
            new.advance(t)
            ref.advance(t)
        elif op == "truncate":
            grants = new.active_grants()
            if grants and rng.random() < 0.8:
                dev = sorted(grants)[int(rng.integers(len(grants)))]
            end = t + float(rng.uniform(0.0, 1.0))
            new.truncate(dev, end)
            ref.truncate(dev, end)
        elif op == "escalate":
            need = idle[dev] + float(rng.uniform(0.0, 400.0))
            assert new.escalate(dev, need, t) == ref.escalate(dev, need, t)
        elif op in ("release_cap", "reclaim"):
            r = int(rng.integers(len(_racks_of(new))))
            a, b = _racks_of(new)[r], _racks_of(ref)[r]
            if op == "reclaim":
                assert a.reclaim_unused() == b.reclaim_unused()
            else:
                w = float(rng.uniform(0.0, 200.0))
                assert a.release_cap(w) == b.release_cap(w)
        else:
            j = job()
            queue = [(0, i, job()) for i in range(int(rng.integers(6)))]
            assert new.offer(dev, j, t, queue) == ref.offer(dev, j, t, queue)
            assert new.potential_w(dev) == ref.potential_w(dev)
        _same_state(new, ref, new_recs, ref_recs, t)
    return counts


class TestEventDrivenGrantState:
    """The release heap and the cached per-rack sums and counts give the
    same caps, grants and stats as rescanning every rack on every call."""

    @pytest.mark.parametrize("n_racks", [1, 8, 32])
    @pytest.mark.parametrize("share", FACILITY_SHARE_POLICIES)
    def test_facility_matches_rescanning_reference(self, share, n_racks):
        for seed in range(3):
            grant = GRANT_POLICIES[seed % len(GRANT_POLICIES)]
            size = 4 if n_racks < 32 else 2
            n_dev = size * n_racks
            # from a cap that binds hard to one that never does
            cap = 60.0 * n_dev + (30.0, 90.0, 400.0)[seed] * n_dev
            kw = dict(share_policy=share, grant_policy=grant)
            new = FacilityCoordinator(cap, [size] * n_racks, **kw)
            ref = _RescanFacility(cap, [size] * n_racks, **kw)
            counts = _drive(new, ref, n_dev, seed=100 * n_racks + seed)
            assert min(counts.values()) > 0, counts
            if n_racks > 1 and share != "static":
                assert new.stats.rebalances == counts["advance"]

    @pytest.mark.parametrize("grant", GRANT_POLICIES)
    def test_bare_coordinator_matches_rescanning_reference(self, grant):
        for seed in range(3):
            cap = (300.0, 600.0, math.inf)[seed]
            new = PowerCapCoordinator(cap, grant_policy=grant)
            ref = _RescanRack(cap, grant_policy=grant)
            _drive(new, ref, 6, seed=seed)

    @pytest.mark.parametrize("preempt", [False, True])
    @pytest.mark.parametrize("share", FACILITY_SHARE_POLICIES)
    def test_engine_schedule_matches_rescanning_reference(
            self, share, preempt, testbed, fitted, app_feats):
        """32 racks of 2 under a cap that binds: the same records, grants
        and stats as the rescanning coordinator, on the plain loop and on
        the preemptive loop (where preemption truncates leases)."""
        n_dev, racks = 64, [2] * 32
        jobs = list(multi_rack_workload(APPS, testbed, n_devices=n_dev,
                                        n_jobs=240, seed=3))
        cap = 64 * testbed.idle_power() + 40.0 * n_dev
        out = []
        for cls in (FacilityCoordinator, _RescanFacility):
            fac = cls(cap, racks, share_policy=share)
            pre = (FederatedPreemptionManager(
                racks, dvfs=V5E_DVFS, device_slowdown={3: 4.0, 40: 3.0})
                if preempt else None)
            r = run_schedule(jobs, "risk-aware", Testbed(seed=1000),
                             predictor=fitted, app_features=app_feats,
                             n_devices=n_dev, power_coordinator=fac,
                             preemption=pre)
            out.append((fac, r))
        (new, r1), (ref, r2) = out
        assert len(r1.records) == len(r2.records) > 0
        for a, b in zip(r1.records, r2.records):
            assert (a.job_id, a.device, a.clock, a.start, a.end,
                    a.power_grant_w) == (b.job_id, b.device, b.clock,
                                         b.start, b.end, b.power_grant_w)
        assert new.caps() == ref.caps()
        assert new.rack_stats() == ref.rack_stats()
        for f in _FACILITY_FIELDS:
            assert getattr(new.stats, f) == getattr(ref.stats, f), f
        assert new.stats.escalations > 0       # the cap binds
        if preempt:
            assert any(rec.preempted for rec in r1.records)

    def test_counters_count_calls_and_changed_racks(self):
        fac = FacilityCoordinator(1000.0, [2, 2, 2, 2])
        fac.reset([20.0] * 8)
        visited = []
        for rack in fac.racks:
            def spy(t, i=rack.index, advance=rack.coord.advance):
                visited.append(i)
                return advance(t)
            rack.coord.advance = spy
        fac.advance(0.0)                   # nothing changed since reset
        assert (fac.stats.advances, fac.stats.rack_refreshes) == (1, 0)
        fac.commit(0, 100.0, end=5.0, drawn_w=90.0)     # rack 0
        fac.commit(5, 100.0, end=3.0, drawn_w=90.0)     # rack 2
        fac.commit(4, 100.0, end=8.0, drawn_w=90.0)     # rack 2
        fac.advance(1.0)
        assert fac.stats.rack_refreshes == 2
        fac.advance(2.0)
        fac.truncate(0, 4.0)               # a lease moves, no watts do
        fac.advance(3.5)                   # rack 2 releases device 5
        assert fac.stats.rack_refreshes == 3
        fac.advance(4.0)                   # rack 0 releases device 0
        assert (fac.stats.advances, fac.stats.rack_refreshes) == (5, 4)
        # only racks with a lease end due were ever visited: rack 2 once,
        # rack 0 at the truncated end and at its stale original end
        fac.advance(10.0)
        assert sorted(set(visited)) == [0, 2]
        assert (fac.stats.advances, fac.stats.rack_refreshes) == (6, 5)
        assert fac.active_grants() == {}
