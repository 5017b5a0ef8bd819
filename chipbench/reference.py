"""The comparison that decides ``correct``.

Three things are compared, after the window has closed:

* Prediction tables. Every (app, device class) ladder table the run's
  service holds, built in set-up or in the window, by the kernel or by
  numpy, against a plain traversal of the fitted ensembles written here:
  ``leaf = sum_l (x[f_l] > t_l) << l`` per oblivious tree, the selected
  leaf values of a row summed in float64 as numpy sums a row. The fitted predictor, the feature
  encoding and the clock features are the system's inputs and are read as
  they are; no table, leaf index or prediction of the program is used.
* The schedule's guarantees, worked out here from the records, the jobs
  the window handed over and the benchmark's frozen copy of the fleet's
  physics (``chipbench/physics.py``): every job placed exactly once (a
  shed job too), no job shed whose tier the mix does not mark sheddable, no
  device running two jobs at once or a job before it arrived, each
  record's time, draw and energy those of its app at its clock with the
  run's measurement noise replayed, misses counted from the deadlines,
  and the coordinator's granted draw never above the facility cap.
* The schedule against the program's scalar decision path. The jobs the
  window handed to the engine are replayed through it with numpy
  prediction on a fresh service, testbed and coordinator, and records,
  misses, shed and energy must be equal.

The table and schedule comparisons are exact: the kernel returns leaf
indices and the host sums them as numpy does, so any difference is a
fault.
"""
from __future__ import annotations

import collections

import numpy as np

from repro.core.features import clock_features

from . import physics

#: Float slack (W) of the cap check: the granted draw is a sum of some
#: hundred grants of up to a few hundred watts, summed here in another
#: order than the coordinator sums it.
CAP_SLACK_W = 1e-6


def traverse(model, Xe: np.ndarray) -> np.ndarray:
    """Plain oblivious-ensemble prediction: float64 features against the
    stored thresholds, then each row's selected leaf values summed in
    float64. The sum is numpy's row sum of a C-ordered array, the order
    the program's numpy path uses; another order differs in the last bit,
    and that is enough to change a schedule."""
    Xe = np.asarray(Xe, dtype=np.float64)
    n_trees, depth = model.feats.shape
    idx = np.zeros((Xe.shape[0], n_trees), dtype=np.int64)
    for level in range(depth):
        bit = Xe[:, model.feats[:, level]] > model.thresholds[:, level]
        idx |= bit.astype(np.int64) << level
    vals = np.ascontiguousarray(model.leaves[np.arange(n_trees), idx])
    return model.base + vals.sum(axis=1)


def predict(target, X: np.ndarray) -> np.ndarray:
    """One regressor of the predictor through :func:`traverse`."""
    Xe = target.enc.transform(X) if target.enc is not None else X
    return target._decode_target(X, traverse(target.gbdt, Xe))


def table(predictor, feats: np.ndarray, device_class) -> tuple:
    """Reference ``(P, T)`` ladder of one app on one device class."""
    d = device_class.dvfs
    X = np.stack([np.concatenate([feats, clock_features(c, d)])
                  for c in d.clock_list()])
    return predict(predictor.power, X), predict(predictor.time, X)


def tables_differ(service, predictor, features: dict, names, classes
                  ) -> tuple[int, int, int]:
    """``(differ, missing, compared)``: tables of the service unequal to
    the reference, tables the run should have built but did not, and
    tables compared."""
    differ = missing = compared = 0
    for name in names:
        for cls in classes:
            builds = service.stats.table_builds
            got = service.base_table(name, cls)
            if service.stats.table_builds != builds:
                missing += 1
            P, T = table(predictor, features[name], cls)
            compared += 1
            if not (np.array_equal(got.P, P) and np.array_equal(got.T, T)):
                differ += 1
    return differ, missing, compared


def schedule_gaps(got, want) -> dict[str, float]:
    """Exact differences of two schedule results."""
    differ = sum(a != b for a, b in zip(got.records, want.records))
    differ += abs(len(got.records) - len(want.records))
    return {
        "records_differ": differ,
        "misses_gap": abs(got.misses - want.misses),
        "shed_gap": abs(got.shed_count - want.shed_count),
        "energy_gap_j": abs(got.total_energy - want.total_energy),
    }


def guarantees(result, jobs, pool, cap_w: float, seed: int,
               noise: float, sheddable=frozenset()) -> dict[str, float]:
    """The schedule's guarantees, each as a count or an excess that is 0
    when it holds. ``jobs`` are the jobs handed to the engine, ``pool`` the
    positional device classes, ``seed`` and ``noise`` the engine's
    measurement noise (one time draw, then one draw of power, per dispatch
    in dispatch order), ``sheddable`` the names of the tiers the traffic
    lets admission control shed."""
    by_id = {j.job_id: j for j in jobs}
    shed_unsheddable = sum(by_id[j.job_id].tier.name not in sheddable
                           for j in result.shed if j.job_id in by_id)
    placed = collections.Counter(r.job_id for r in result.records)
    placed.update(j.job_id for j in result.shed)
    misplaced = sum(placed.get(i, 0) != 1 for i in by_id)
    misplaced += sum(n for i, n in placed.items() if i not in by_id)

    overlaps = 0
    last_end: dict[int, float] = {}
    for r in sorted(result.records, key=lambda r: (r.device, r.start)):
        job = by_id.get(r.job_id)
        if job is None or (r.name, r.arrival, r.deadline) != (
                job.name, job.arrival, job.deadline):
            misplaced += 1
            continue
        if r.start < job.arrival or r.start < last_end.get(r.device, 0.0):
            overlaps += 1
        last_end[r.device] = r.end

    rng = np.random.default_rng(seed)
    truth: dict = {}
    ladders: dict = {}
    physics_differ = 0
    for r in result.records:
        d = pool[r.device].dvfs
        if id(d) not in ladders:
            ladders[id(d)] = set(d.clock_list())
        app = by_id[r.job_id].app if r.job_id in by_id else None
        n_t, n_p = rng.normal(), rng.normal()
        if app is None or r.clock not in ladders[id(d)]:
            physics_differ += 1
            continue
        key = (app.name, app.seed, id(d), r.clock)
        if key not in truth:
            truth[key] = (physics.true_time(app, r.clock, d),
                          physics.true_power(app, r.clock, d))
        t0, p0 = truth[key]
        t = max(t0 * (1 + noise * n_t), 1e-6)
        p = max(p0 * (1 + noise * n_p), 1.0)
        if (r.time_s, r.power_w, r.energy_j, r.end) != (
                t, p, t * p, r.start + t) or (
                r.device_class not in (None, pool[r.device].name)):
            physics_differ += 1

    recount = sum(r.end > by_id[r.job_id].deadline + 1e-9
                  for r in result.records
                  if not r.preempted and r.job_id in by_id)

    idle = [c.idle_power() for c in pool]
    grants_missing = sum(r.power_grant_w is None for r in result.records)
    events = []
    for r in result.records:
        if r.power_grant_w is not None:
            extra = r.power_grant_w - idle[r.device]
            events.append((r.start, 1, extra))
            events.append((r.end, 0, -extra))
    events.sort(key=lambda e: (e[0], e[1]))
    drawn = np.cumsum([e[2] for e in events]) if events else np.zeros(1)
    excess = max(0.0, float(sum(idle) + drawn.max()) - cap_w)
    return {"jobs_misplaced": misplaced, "overlaps": overlaps,
            "physics_differ": physics_differ,
            "misses_recount": abs(result.misses - int(recount)),
            "grants_missing": grants_missing, "cap_excess_w": excess,
            "shed_unsheddable": shed_unsheddable}
