"""The benchmark's one traffic generator: a frozen copy of the program's
bursty multi-rack stream, plus first submissions of never-profiled apps.

Every traffic mix is a JSON file of parameters under ``chipbench/traffic``
that this generator reads; a mix never brings code of its own.

The stream is ``repro.core.workload.multi_rack_workload`` as it stood when
copied: bursts of part of the pool at exponential gaps, apps drawn
uniformly from the suite, deadlines from a virtual default-clock dispatch
plus a uniform slack, a checkpoint quantum per job. With no new apps it
equals that function job for job at the same seed. A new app is a variant
of the suite app its job drew, decided and drawn from a second random
stream, so the arrivals and the base apps do not depend on the share.

A mix may also list SLA tiers (``tiers``). Each job's tier is drawn from a
third random stream, and a tiered job's deadline is anchored at its
arrival, ``arrival + (1 + U[tier.slack_range]) * t_a``, the rule of
``repro.core.workload.multi_tenant_workload``: under overload a virtual
default-clock anchor drifts away with the backlog and makes every deadline
loose. Arrivals, apps, job ids and quanta stay those of the untiered
stream, and without ``tiers`` every job carries the inert default tier.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, Optional, Sequence

import numpy as np

from repro.core import (DEFAULT_TIER, AppProfile, DeviceClass, Job,
                        Testbed, TierSpec)

#: AppProfile seeds of novel apps start here, clear of the suite's seeds.
NOVEL_SEED_BASE = 1_000_000


def novel_app(base: AppProfile, index: int, rng: np.random.Generator,
              latents: dict) -> AppProfile:
    """A never-profiled variant of ``base``: the same static counters and
    new latents drawn uniformly from ``latents`` (a copy of the variant
    maker of ``benchmarks/bench_coldstart.py``)."""
    return dataclasses.replace(
        base, name=f"novel-{index}", seed=NOVEL_SEED_BASE + index,
        stall_frac=float(rng.uniform(*latents["stall_frac"])),
        core_eff=float(rng.uniform(*latents["core_eff"])),
        mem_eff=float(rng.uniform(*latents["mem_eff"])),
        wiggle_time=float(latents["wiggle_time"]),
        wiggle_power=float(latents["wiggle_power"]))


def tiers_of(traffic: dict) -> tuple[list[TierSpec], np.ndarray]:
    """The mix's tiers, built from its data alone, and their cumulative
    shares, normalised to end at 1 (empty without ``tiers``)."""
    specs, shares = [], []
    for t in traffic.get("tiers", ()):
        specs.append(TierSpec(
            str(t["name"]), priority=int(t["priority"]),
            weight=float(t["weight"]), sheddable=bool(t["sheddable"]),
            slack_range=tuple(float(x) for x in t["slack_range"])))
        shares.append(float(t["share"]))
    cum = np.cumsum(shares)
    return specs, cum / cum[-1] if specs else cum


def stream(suite: Sequence[AppProfile], testbed: Testbed,
           pool: Sequence[DeviceClass], traffic: dict, seed: int,
           novel_seed: int = 0, tier_seed: int = 0,
           on_novel: Optional[Callable[[AppProfile], None]] = None,
           stop: Optional[Callable[[], bool]] = None,
           n_jobs: Optional[int] = None) -> Iterator[Job]:
    """Jobs in nondecreasing arrival order over the explicit ``pool``.

    ``traffic`` keys: ``burst_frac`` (jobs per burst as a share of the
    pool), ``utilization``, ``slack_range`` and ``quantum_frac`` as in
    ``multi_rack_workload``; ``novel_share`` (the chance that a job is the
    first submission of a new app) and ``novel_latents``; ``tiers``, each
    with ``name``, ``priority``, ``weight``, ``sheddable``, ``slack_range``
    and ``share``, drawn from ``tier_seed``. ``on_novel`` is
    called with each new app before its job is yielded (the harness
    profiles and registers it there). The stream ends when ``stop()`` turns
    true, checked before each job, or after ``n_jobs`` jobs."""
    rng = np.random.default_rng(seed)
    nrng = np.random.default_rng(novel_seed)
    trng = np.random.default_rng(tier_seed)
    tiers, cum = tiers_of(traffic)
    share = float(traffic.get("novel_share", 0.0))
    latents = traffic.get("novel_latents")
    burst = max(1, int(len(pool) * float(traffic["burst_frac"])))
    slack_range = tuple(float(x) for x in traffic["slack_range"])
    quantum_frac = float(traffic["quantum_frac"])
    by_cls: dict[str, np.ndarray] = {}
    for cls in pool:
        if cls.name not in by_cls:
            by_cls[cls.name] = np.array([
                testbed.true_time(a, cls.dvfs.default_clock, dvfs=cls.dvfs)
                for a in suite])
    t_dc_dev = [by_cls[cls.name] for cls in pool]
    rate = sum(1.0 / float(t.mean()) for t in t_dc_dev)
    mean_interburst = burst / (rate * float(traffic["utilization"]))
    dev_free = np.zeros(len(pool))
    now, jid, n_novel = 0.0, 0, 0
    while n_jobs is None or jid < n_jobs:
        now += float(rng.exponential(mean_interburst))
        for _ in range(burst if n_jobs is None else min(burst, n_jobs - jid)):
            if stop is not None and stop():
                return
            idx = int(rng.integers(len(suite)))
            dev = int(np.argmin(dev_free))      # virtual DC dispatch
            app = suite[idx]
            if share > 0.0 and nrng.random() < share:
                app = novel_app(app, n_novel, nrng, latents)
                n_novel += 1
                if on_novel is not None:
                    on_novel(app)
                cls = pool[dev]
                t_a = float(testbed.true_time(
                    app, cls.dvfs.default_clock, dvfs=cls.dvfs))
            else:
                t_a = float(t_dc_dev[dev][idx])
            done = max(float(dev_free[dev]), now) + t_a
            dev_free[dev] = done
            deadline = done + float(rng.uniform(*slack_range)) * t_a
            tier = DEFAULT_TIER
            if tiers:
                k = int(np.searchsorted(cum, trng.random()))
                tier = tiers[min(k, len(tiers) - 1)]
                deadline = now + (1.0 + float(
                    trng.uniform(*tier.slack_range))) * t_a
            yield Job(app=app, arrival=now, deadline=deadline, job_id=jid,
                      checkpoint_quantum=quantum_frac * t_a, tier=tier)
            jid += 1
