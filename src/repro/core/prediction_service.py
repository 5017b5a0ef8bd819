"""Batched, memoized power/time prediction service.

Algorithm 1 (paper §IV) re-predicts power & time for every queued job over
the full clock ladder at every scheduling decision — O(jobs × clocks) model
calls per tick. But the inputs are pure functions of the *application* (its
profiled feature vector) and the *clock pair*: for a fixed trained predictor
the whole per-app ladder table is immutable. This service precomputes it
once per distinct app in one vectorized call and serves every subsequent
decision from cache:

* :meth:`table` — the full ``(P, T)`` ladder table for an app (predicted,
  correlation-index indirection applied, memoized per resolved profile).
* :meth:`t_min` / :meth:`t_dc` — cached point predictions at the max /
  default clock (the queue-aware budget and virtual-pacing inputs).
* :meth:`truth_table` / :meth:`true_t_min` / :meth:`true_t_dc` — the
  ground-truth analogues for the oracle policy (memoized testbed sweeps).

Large batches route through the Pallas one-hot-matmul GBDT kernel
(:mod:`repro.kernels.gbdt_predict`) when the default backend is a TPU; a
kernel failure there raises. Every other batch, and every batch on other
backends, takes the vectorized numpy path (bit-identical to calling the
predictor directly). Set ``use_kernel=True`` to force the kernel
(interpret mode on CPU).

:class:`ServiceStats` counts builds vs hits — the scheduling benchmarks
assert at most one table build per distinct app.

**Online correction layer (PR 2).** An attached corrector (see
:mod:`repro.core.online`) multiplies measurement-feedback scale factors onto
the frozen base table. The base cache is never touched by feedback; the
corrected view lives in a separate per-app cache with an explicit
:meth:`invalidate` API the feedback loop calls when corrections change.

Invariants (the contracts tests/test_online.py and tests/test_engine.py pin):

* **Cache-key contract.** Base tables are keyed by the *resolved profile*
  (``("own", name)`` or ``("corr", correlated_name)`` — see
  :meth:`resolve`) **plus the device-class key**, so correlated apps share
  one build per class. Every cached base quantity (tables, ``t_min``/
  ``t_dc`` points, truth sweeps) is a pure function of ``(predictor, app
  profile, DVFS config)`` and therefore never invalidates: a service may
  be reused across runs indefinitely.
* **Device-class keying (PR 3).** Every query takes an optional
  :class:`~repro.core.dvfs.DeviceClass`; ``None`` — or any class whose
  dvfs equals the service's own with no per-class features — normalizes to
  the same key (:meth:`register_class`), so uniform pools of the baseline
  class hit the very same cache entries as the classless path. Distinct
  classes get their own ladder, feature matrix, and cache rows, built once
  each, with the same build-once semantics.
* **Corrected tables are keyed by (app name, class key)** (corrections are
  per-(app, class) even when base tables are shared via correlation) and
  invalidate only through :meth:`invalidate` — which drops the app across
  every class; the next :meth:`table` call re-applies the corrector's
  *current* correction to the cached base (no predictor re-run). A served
  corrected table always reflects every observation up to the most recent
  invalidation of that app.
* **Frozen-path identity.** With no corrector attached — or an attached
  corrector holding zero observations (its scale is exactly ``exp(0)``) —
  :meth:`table` output is bit-identical to the pre-feedback service.
* **Cold-start tier (PR 8).** An attached
  :class:`~repro.core.coldstart.ColdStartSynthesizer` makes unprofiled
  apps resolvable: :meth:`resolve` returns a ``("cold", name)`` key with
  the app's static embedding, :meth:`base_table` builds the analytic
  roofline ladder (``source="synthesized"``) instead of calling the
  predictor, and the correction layer refines it exactly like a profiled
  table. Profiled apps never touch the synthesizer — attaching one
  changes no profiled-app decision (invariant #10,
  docs/architecture.md). Unknown apps with no synthesizer coverage raise
  a typed :class:`UnknownAppError` carrying the nearest profiled name.
"""
from __future__ import annotations

import collections
import dataclasses
import difflib
import os
from typing import Optional, Sequence

import jax
import numpy as np

from . import tracing
from .correlate import CorrelationIndex
from .dvfs import ClockPair, DVFSConfig, DeviceClass
from .features import clock_features
from .predictor import EnergyTimePredictor
from .simulator import AppProfile, Testbed

__all__ = ["ClockTable", "StackedTable", "ServiceStats", "PredictionService",
           "UnknownAppError", "DEFAULT_KERNEL_MIN_ROWS",
           "KERNEL_MIN_ROWS_ENV", "kernel_min_rows_default"]


class UnknownAppError(KeyError):
    """An app has no profiled feature vector and no attached cold-start
    synthesizer covers it. Subclasses :class:`KeyError` for back-compat
    with callers that caught the old bare ``KeyError``; the message names
    the nearest profiled app (closest-spelled name) so a mis-keyed job is
    diagnosable from the traceback alone."""

    def __init__(self, name: str, known=()):
        self.name = name
        matches = difflib.get_close_matches(name, list(known), n=1,
                                            cutoff=0.0)
        self.suggestion = matches[0] if matches else None
        msg = (f"unknown app {name!r}: no profiled feature vector and no "
               "cold-start synthesizer registration for it")
        if self.suggestion is not None:
            msg += f" (nearest profiled app: {self.suggestion!r})"
        else:
            msg += " (no profiled apps at all)"
        super().__init__(msg)

    def __str__(self) -> str:   # KeyError wraps its arg in quotes — undo
        return self.args[0]

#: Measured batch-routing threshold for the Pallas GBDT kernel
#: (:mod:`repro.kernels.gbdt_predict`): predictor batches with at least
#: this many rows go through the one-hot-matmul kernel when a TPU backend
#: is present. The default is sized from the microbench in
#: ``benchmarks/bench_decide.py`` (``kernel_threshold`` section): a single
#: ladder-table build is 64 rows (v5e) — far too small to amortize a
#: kernel launch — while the multi-app :meth:`PredictionService.
#: prefetch_tables` batches (8+ apps × 64 clocks ≥ 512 rows) sit exactly
#: at the measured spill point where the numpy GBDT path leaves its
#: cache-resident regime (per-row cost degrades several-fold past ~512
#: rows on the reference host — the MXU matmul formulation does not). On
#: CPU the kernel only runs in interpret mode, so auto-routing
#: additionally requires a real TPU.
DEFAULT_KERNEL_MIN_ROWS = 512

#: Environment override for the threshold (an integer; values ≤ 0 route
#: every batch): lets a deployment retune the crossover without code
#: changes after running the bench_decide microbench on its own hardware.
KERNEL_MIN_ROWS_ENV = "REPRO_GBDT_KERNEL_MIN_ROWS"


def kernel_min_rows_default() -> int:
    """The effective default kernel-routing threshold: the env override
    when set (and parseable), else :data:`DEFAULT_KERNEL_MIN_ROWS`."""
    raw = os.environ.get(KERNEL_MIN_ROWS_ENV)
    if raw:
        try:
            return int(raw)
        except ValueError:
            pass
    return DEFAULT_KERNEL_MIN_ROWS


@dataclasses.dataclass(frozen=True)
class ClockTable:
    """Immutable per-app ladder table: ``P[i]``/``T[i]`` at ``clocks[i]``."""

    clocks: tuple[ClockPair, ...]
    P: np.ndarray                 # predicted/true power (W) per clock
    T: np.ndarray                 # predicted/true time (s) per clock
    source: str = "predicted"     # "predicted"|"truth"|"corrected"
                                  # |"synthesized" (cold-start tier)

    def __len__(self) -> int:
        return len(self.clocks)

    @property
    def E(self) -> np.ndarray:
        return self.P * self.T

    def remnant(self, work_frac: float,
                overhead_s: float = 0.0) -> "ClockTable":
        """The table re-expressed for a resumable remnant covering
        ``work_frac`` of the job's work: ``T' = work_frac * T +
        overhead_s``, power per clock unchanged (a remnant draws what
        the app draws). The single definition of the remnant lens —
        :meth:`~repro.core.preemption.PreemptionManager.remnant_view`
        and :meth:`~repro.core.policies.Policy.select_resume` both
        delegate here, so remnant pricing can never drift between the
        engine's resume path and the policy API."""
        return ClockTable(clocks=self.clocks, P=self.P,
                          T=self.T * work_frac + overhead_s,
                          source=self.source)


@dataclasses.dataclass(frozen=True)
class StackedTable:
    """Padded/masked (candidate × clock) tensor view over per-(app, class)
    :class:`ClockTable` rows — the batched decision core's input (PR 6).

    Component ladders of different lengths (v5e: 64 clocks, v5lite: 24)
    are padded to a common width with ``+inf`` in both ``P`` and ``T``
    (``mask`` False there), so a feasibility test ``T' <= budget`` can
    never admit a padded slot and a masked row minimum ignores it. The
    component tables are retained for identity checks (a stacked view is
    valid only while every row *is* the table a decision would fetch) and
    for recovering exact per-row clock objects after an argmin."""

    tables: tuple[ClockTable, ...]
    P: np.ndarray                 # (C, Lmax) padded power, pad = +inf
    T: np.ndarray                 # (C, Lmax) padded time, pad = +inf
    mask: np.ndarray              # (C, Lmax) bool, True on real entries
    lengths: tuple[int, ...]

    def __len__(self) -> int:
        return len(self.tables)

    @classmethod
    def from_tables(cls, tables: Sequence[ClockTable]) -> "StackedTable":
        tables = tuple(tables)
        lengths = tuple(len(t) for t in tables)
        C, L = len(tables), max(lengths)
        P = np.full((C, L), np.inf)
        T = np.full((C, L), np.inf)
        mask = np.zeros((C, L), dtype=bool)
        for i, t in enumerate(tables):
            n = lengths[i]
            P[i, :n] = t.P
            T[i, :n] = t.T
            mask[i, :n] = True
        return cls(tables=tables, P=P, T=T, mask=mask, lengths=lengths)


@dataclasses.dataclass
class ServiceStats:
    table_builds: int = 0         # vectorized ladder-table constructions
    table_hits: int = 0           # decisions served from cache
    truth_builds: int = 0
    truth_hits: int = 0
    point_predictions: int = 0    # cached single-row t_min / t_dc predicts
    rows_predicted: int = 0       # total predictor rows evaluated
    kernel_batches: int = 0       # batches routed through the Pallas kernel
    corrected_builds: int = 0     # corrected-view (re)applications
    corrected_hits: int = 0       # decisions served from the corrected cache
    invalidations: int = 0        # targeted corrected-cache invalidations
    stacked_builds: int = 0       # stacked (candidate x clock) view builds
    stacked_hits: int = 0         # joint decisions served from stacked cache
    prefetched_tables: int = 0    # tables built via batched prefetch
    synthesized_builds: int = 0   # cold-start analytic ladder builds
    kernel_cells: int = 0         # (row, tree) cells sent to the kernel
    kernel_padded_cells: int = 0  # (row, tree) cells it computed, padded

    def summary(self) -> str:
        return (f"table_builds={self.table_builds} hits={self.table_hits} "
                f"truth_builds={self.truth_builds} "
                f"rows={self.rows_predicted} kernel={self.kernel_batches} "
                f"corrected={self.corrected_builds}"
                f"/{self.corrected_hits}hit "
                f"invalidations={self.invalidations}")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class PredictionService:
    """Shared prediction layer for schedulers; safe to reuse across runs —
    every cached quantity is a deterministic function of (predictor, app
    profile, DVFS config)."""

    def __init__(
        self,
        dvfs: DVFSConfig,
        predictor: Optional[EnergyTimePredictor] = None,
        app_features: Optional[dict[str, np.ndarray]] = None,
        corr_index: Optional[CorrelationIndex] = None,
        corr_features: Optional[dict[str, np.ndarray]] = None,
        testbed: Optional[Testbed] = None,
        use_kernel: bool | str = "auto",
        kernel_min_rows: Optional[int] = None,
        class_features: Optional[dict[str, dict[str, np.ndarray]]] = None,
        stacked_cache_size: int = 128,
    ):
        self.dvfs = dvfs
        self.predictor = predictor
        self.app_features = app_features
        self.corr_index = corr_index
        self.corr_features = corr_features
        self.testbed = testbed
        self.use_kernel = use_kernel
        # None → the module default, overridable via KERNEL_MIN_ROWS_ENV
        self.kernel_min_rows = int(kernel_min_rows
                                   if kernel_min_rows is not None
                                   else kernel_min_rows_default())
        self.stacked_cache_size = int(stacked_cache_size)
        #: per-class app profile vectors (``{class_name: {app: feats}}``) —
        #: the "profile once per device class" campaign. Apps/classes not
        #: listed fall back to the shared ``app_features`` (+ correlation).
        self.class_features = class_features or {}
        self.stats = ServiceStats()
        self._waves = 0               # prefetch waves the recorder saw

        self.clocks: tuple[ClockPair, ...] = tuple(dvfs.clock_list())
        self._clock_X = [clock_features(c, dvfs) for c in self.clocks]
        self._corrector = None
        self._synthesizer = None
        # corrected views keyed (app name, class key); base tables keyed
        # (resolved profile key, class key). class key None = the service's
        # own dvfs — a DeviceClass wrapping the same config normalizes to
        # None, so uniform pools share today's cache entries bit-for-bit.
        self._corrected: dict[tuple[str, Optional[str]], ClockTable] = {}
        # stacked (candidate x clock) views, LRU-bounded; entries carry the
        # correction epoch they were built at — any corrector attach/detach/
        # invalidate bumps the epoch and lazily voids every stacked view
        # without scanning the cache (base/truth tables never invalidate,
        # so epoch-stale entries simply rebuild from the same components)
        self._stacked: "collections.OrderedDict[tuple, tuple[int, StackedTable]]" = (
            collections.OrderedDict())
        self._epoch = 0
        self._tables: dict[tuple, ClockTable] = {}
        self._truth: dict[tuple, ClockTable] = {}
        self._resolved: dict[str, tuple[tuple, np.ndarray]] = {}
        self._tmin: dict[tuple, float] = {}
        self._tdc: dict[tuple, float] = {}
        self._true_tmin: dict[tuple, float] = {}
        self._true_tdc: dict[tuple, float] = {}
        self._classes: dict[str, DeviceClass] = {}
        self._ladder_index: dict[
            Optional[str], dict[ClockPair, int]] = {}
        self._class_keys: dict[str, Optional[str]] = {}
        self._seen_class_dvfs: dict[str, DVFSConfig] = {}
        self._class_clocks: dict[
            str, tuple[tuple[ClockPair, ...], list[np.ndarray]]] = {}

    # ------------------------------------------------------------------ #
    @property
    def has_predictor(self) -> bool:
        return self.predictor is not None and self.app_features is not None

    def resolve(self, name: str) -> tuple[tuple, np.ndarray]:
        """Profile vector used to predict for ``name``: the app's own
        default-clock profile, or — when a correlation index is configured —
        the correlated exhaustively-profiled app's vector (paper §III-D).

        Unprofiled apps resolve to ``("cold", name)`` with their static
        embedding when the attached synthesizer has them registered
        (correlation indirection deliberately skipped — the cold tier does
        its own nearest-profiled mapping); otherwise a typed
        :class:`UnknownAppError` is raised."""
        hit = self._resolved.get(name)
        if hit is not None:
            return hit
        feats = (self.app_features or {}).get(name)
        if feats is None:
            synth = self._synthesizer
            if synth is not None and synth.knows(name):
                resolved = (("cold", name), synth.static_features_of(name))
                self._resolved[name] = resolved
                return resolved
            raise UnknownAppError(name, known=self.app_features or ())
        key = ("own", name)
        if self.corr_index is not None and self.corr_features is not None:
            corr_name = self.corr_index.correlated(feats, exclude=name)
            if corr_name in self.corr_features:
                feats = self.corr_features[corr_name]
                key = ("corr", corr_name)
        self._resolved[name] = (key, feats)
        return key, feats

    # ------------------------------------------------------------------ #
    #  Device classes
    # ------------------------------------------------------------------ #
    def register_class(self, device_class: Optional[DeviceClass]
                       ) -> Optional[str]:
        """Normalize a device class to its cache key.

        Returns ``None`` when the class is indistinguishable from the
        service's own dvfs (same ladder, same electrical model, no per-class
        feature overrides) — those classes share the base caches, which is
        what makes a uniform pool of the baseline class bit-identical to the
        classless path. Distinct classes get their own ladder feature matrix
        built once here."""
        if device_class is None:
            return None
        name = device_class.name
        if name in self._class_keys:
            seen = self._seen_class_dvfs[name]
            if seen is not device_class.dvfs and seen != device_class.dvfs:
                raise ValueError(
                    f"conflicting DeviceClass {name!r}: two classes with "
                    "the same name but different DVFS configs")
            return self._class_keys[name]
        self._seen_class_dvfs[name] = device_class.dvfs
        if (device_class.dvfs == self.dvfs
                and name not in self.class_features):
            self._class_keys[name] = None
            return None
        self._class_keys[name] = name
        self._classes[name] = device_class
        clocks = tuple(device_class.dvfs.clock_list())
        self._class_clocks[name] = (
            clocks, [clock_features(c, device_class.dvfs) for c in clocks])
        return name

    def device_class(self, name: Optional[str]) -> Optional[DeviceClass]:
        """The registered class for ``name`` (None for unknown names and
        for classes normalized onto the service's own dvfs)."""
        return self._classes.get(name) if name is not None else None

    def clocks_for(self, class_key: Optional[str]) -> tuple[ClockPair, ...]:
        """The ladder a class's tables are indexed by."""
        if class_key is None:
            return self.clocks
        return self._class_clocks[class_key][0]

    def _class_dvfs(self, class_key: Optional[str]) -> DVFSConfig:
        return (self.dvfs if class_key is None
                else self._classes[class_key].dvfs)

    def _feats_for(self, name: str, class_key: Optional[str]
                   ) -> tuple[tuple, np.ndarray]:
        """Profile vector for ``(app, class)``: the per-class profiling
        campaign when one was supplied, else the shared default-class
        profile (with correlation indirection, exactly as before)."""
        if class_key is not None:
            over = self.class_features.get(class_key)
            if over is not None and name in over:
                return ("cls", class_key, name), over[name]
        return self.resolve(name)

    @staticmethod
    def _correction_key(name: str, class_key: Optional[str]) -> str:
        """The key the online layer files corrections under — per app on
        the default class, per (app, class) on explicit classes."""
        return name if class_key is None else f"{name}::{class_key}"

    # ------------------------------------------------------------------ #
    #  Predicted tables
    # ------------------------------------------------------------------ #
    def base_table(self, name: str,
                   device_class: Optional[DeviceClass] = None) -> ClockTable:
        """Frozen-predictor ladder ``(P, T)`` for ``(app, device class)`` —
        one build per distinct (resolved profile, class), every later call
        a cache hit. Never affected by the online correction layer."""
        ck = self.register_class(device_class)
        feat_key, feats = self._feats_for(name, ck)
        key = (feat_key, ck)
        tab = self._tables.get(key)
        if tab is not None:
            self.stats.table_hits += 1
            return tab
        if feat_key[0] == "cold":
            # cold-start tier: analytic roofline ladder from the attached
            # synthesizer — no predictor rows, same cache-key contract
            clocks = self.clocks_for(ck)
            P, T = self._synthesizer.synthesize(
                name, clocks, self._class_dvfs(ck))
            tab = ClockTable(clocks=clocks, P=P, T=T, source="synthesized")
            self.stats.synthesized_builds += 1
        else:
            tab = self.table_for_features(feats, class_key=ck)
        self._tables[key] = tab
        self.stats.table_builds += 1
        return tab

    def table(self, name: str,
              device_class: Optional[DeviceClass] = None) -> ClockTable:
        """The table scheduling decisions consume: the frozen base table,
        with the attached corrector's current per-(app, class) corrections
        applied (cached until :meth:`invalidate`). Without a corrector this
        *is* :meth:`base_table`."""
        ck = self.register_class(device_class)
        base = self.base_table(name, device_class)
        if self._corrector is None:
            return base
        tab = self._corrected.get((name, ck))
        if tab is not None:
            self.stats.corrected_hits += 1
            return tab
        P, T = self._corrector.correct(self._correction_key(name, ck),
                                       base.clocks, base.P, base.T)
        tab = ClockTable(clocks=base.clocks, P=P, T=T, source="corrected")
        self._corrected[(name, ck)] = tab
        self.stats.corrected_builds += 1
        return tab

    def power_at(self, name: str,
                 device_class: Optional[DeviceClass] = None,
                 clocks: Optional[Sequence[ClockPair]] = None) -> np.ndarray:
        """Vectorized predicted power for ``(app, class)`` at ``clocks``
        (default: the class's full ladder) — the power-cap subsystem's
        name-keyed analysis view (cap sizing, predicted-draw
        reconciliation against the telemetry ledger; see bench_powercap).
        Pure table lookup over the same cached rows the engine's cap
        filter reads in-table: the first call per (app, class) builds the
        ladder table, every later call (any clock subset, any order)
        indexes into it — no predictor invocations, so cap arithmetic
        stays as cheap as a scheduling decision."""
        tab = self.table(name, device_class)
        if clocks is None:
            return tab.P
        ck = self.register_class(device_class)
        index = self._ladder_index.get(ck)
        if index is None:
            index = {c: i for i, c in enumerate(self.clocks_for(ck))}
            self._ladder_index[ck] = index
        rows = np.fromiter((index[c] for c in clocks), dtype=np.intp,
                           count=len(clocks))
        return tab.P[rows]

    # ------------------------------------------------------------------ #
    #  Online correction layer
    # ------------------------------------------------------------------ #
    def attach_corrector(self, corrector) -> None:
        """Attach a correction provider (``correct(name, clocks, P, T) →
        (P', T')``, see :mod:`repro.core.online`). Any previously cached
        corrected views are dropped; base caches are untouched."""
        self._corrector = corrector
        self._corrected.clear()
        self._epoch += 1

    def detach_corrector(self) -> None:
        """Remove the correction layer — the service reverts bit-identically
        to the frozen path."""
        self._corrector = None
        self._corrected.clear()
        self._epoch += 1

    @property
    def corrector(self):
        return self._corrector

    # ------------------------------------------------------------------ #
    #  Cold-start tier (PR 8)
    # ------------------------------------------------------------------ #
    def attach_synthesizer(self, synthesizer) -> None:
        """Attach a cold-start table source (see
        :class:`~repro.core.coldstart.ColdStartSynthesizer`): unprofiled
        apps it registers become resolvable, served analytic
        ``source="synthesized"`` base tables that the correction layer
        refines like any profiled table. Profiled apps are unaffected —
        their resolve path never consults the synthesizer."""
        self._synthesizer = synthesizer
        if synthesizer is not None:
            synthesizer.bind(self)
        self._epoch += 1

    def detach_synthesizer(self) -> None:
        """Remove the cold-start tier. Previously synthesized base tables
        stay cached (they are pure functions of frozen inputs); apps that
        only resolved through the synthesizer become unknown again for
        *new* resolutions."""
        self._synthesizer = None
        self._resolved = {n: v for n, v in self._resolved.items()
                          if v[0][0] != "cold"}
        self._epoch += 1

    @property
    def synthesizer(self):
        return self._synthesizer

    def note_app(self, app: AppProfile) -> bool:
        """Admission-time registration hook (the engine calls this on
        every arrival when a synthesizer is attached): profiled apps are
        a dictionary-membership no-op — the zero-unseen-apps identity —
        while unprofiled ones register their static embedding with the
        synthesizer. Returns True when the app was newly registered."""
        if self._synthesizer is None:
            return False
        if self.app_features is not None and app.name in self.app_features:
            return False
        return self._synthesizer.register(app)

    def invalidate(self, name: Optional[str] = None) -> int:
        """Targeted corrected-cache invalidation: drop app ``name``'s
        corrected tables — across every device class — (all apps when
        ``name`` is None) so the next :meth:`table` call re-applies the
        corrector's current correction to the cached base. Returns the
        number of entries dropped. Base tables are pure functions of frozen
        inputs and are deliberately *not* invalidatable."""
        self.stats.invalidations += 1
        self._epoch += 1
        if name is not None and self._synthesizer is not None:
            # observation-driven invalidations are the cold-start
            # promotion clock (cold → warmed); profiled names are a no-op
            self._synthesizer.note_invalidation(name)
        if name is None:
            n = len(self._corrected)
            self._corrected.clear()
            return n
        stale = [k for k in self._corrected if k[0] == name]
        for k in stale:
            del self._corrected[k]
        return len(stale)

    def table_for_features(self, feats: np.ndarray,
                           class_key: Optional[str] = None) -> ClockTable:
        """Uncached vectorized table build from a raw profile vector, over
        the given class's ladder (default: the service's own)."""
        if class_key is None:
            clocks, clock_X = self.clocks, self._clock_X
        else:
            clocks, clock_X = self._class_clocks[class_key]
        X = np.stack([np.concatenate([feats, cx]) for cx in clock_X])
        P = self._predict(self.predictor.power, X)
        T = self._predict(self.predictor.time, X)
        return ClockTable(clocks=clocks, P=P, T=T, source="predicted")

    # ------------------------------------------------------------------ #
    #  Stacked candidate views + batched prefetch (PR 6)
    # ------------------------------------------------------------------ #
    def stacked_tables(self, name_or_app, device_classes: Sequence,
                       kind: str = "predicted") -> StackedTable:
        """The padded/masked per-(app, class-tuple) tensor view the batched
        joint decision scores in one pass (see :class:`StackedTable`).

        Cache-keyed like the per-app tables — ``(kind, app identity, class
        names)``, where identity is the app *name* for predicted tables and
        the frozen profile for truth tables (the same keying rule as
        :meth:`table` vs :meth:`truth_table`) — LRU-bounded by
        ``stacked_cache_size``, and epoch-validated: any corrector attach/
        detach/:meth:`invalidate` voids cached views lazily. Component rows
        are the *same objects* :meth:`table`/:meth:`truth_table` serve, so
        a consumer can verify row identity in O(classes)."""
        classes = tuple(device_classes)
        key = (kind, name_or_app,
               tuple(c.name if c is not None else None for c in classes))
        entry = self._stacked.get(key)
        if entry is not None and entry[0] == self._epoch:
            self._stacked.move_to_end(key)
            self.stats.stacked_hits += 1
            return entry[1]
        if kind == "truth":
            comps = [self.truth_table(name_or_app, c) for c in classes]
        elif kind == "predicted":
            comps = [self.table(name_or_app, c) for c in classes]
        else:
            raise ValueError(f"unknown stacked-table kind {kind!r}")
        stk = StackedTable.from_tables(comps)
        self._stacked[key] = (self._epoch, stk)
        self._stacked.move_to_end(key)
        while len(self._stacked) > self.stacked_cache_size:
            self._stacked.popitem(last=False)
        self.stats.stacked_builds += 1
        return stk

    def prefetch_tables(self, names: Sequence[str],
                        device_classes: Sequence = (None,)) -> int:
        """Build every missing (app, class) base table in **one** stacked
        predictor call per (class, regressor) — the batch shape that routes
        through the Pallas ``gbdt_predict`` kernel when it clears
        ``kernel_min_rows`` (n_missing_apps × ladder rows, vs one ladder at
        a time on the lazy path). Row-identical to building tables one app
        at a time: the GBDT/linear predictors are strictly rowwise, so
        slicing a stacked prediction reproduces the per-app arrays
        bit-for-bit (pinned in tests/test_batch_decide.py).

        Returns the number of tables built (correlated apps sharing a
        resolved profile count once, exactly like :meth:`base_table`).

        With the recorder on, a call that builds a table is one
        ``predict.wave`` span, from its first build to its return."""
        built = 0
        tr = tracing.ON
        wave = None
        for cls in device_classes:
            ck = self.register_class(cls)
            if ck is None:
                clocks, clock_X = self.clocks, self._clock_X
            else:
                clocks, clock_X = self._class_clocks[ck]
            todo: list[tuple[tuple, np.ndarray]] = []
            seen: set = set()
            for name in names:
                feat_key, feats = self._feats_for(name, ck)
                key = (feat_key, ck)
                if key in self._tables or key in seen:
                    continue
                if feat_key[0] == "cold":
                    # synthesized ladders are analytic, not predictor
                    # rows — build individually, keep them out of the
                    # stacked predictor batch
                    if tr and wave is None:
                        wave = self._begin_wave()
                    self.base_table(name, cls)
                    built += 1
                    continue
                seen.add(key)
                todo.append((key, feats))
            if not todo:
                continue
            if tr:
                if wave is None:
                    wave = self._begin_wave()
                span = tracing.begin("predict.stack")
            L = len(clocks)
            X = np.stack([np.concatenate([feats, cx])
                          for _, feats in todo for cx in clock_X])
            if tr:
                tracing.end(span)
            P = self._predict(self.predictor.power, X)
            T = self._predict(self.predictor.time, X)
            if tr:
                span = tracing.begin("predict.store")
            for i, (key, _) in enumerate(todo):
                tab = ClockTable(clocks=clocks,
                                 P=P[i * L:(i + 1) * L].copy(),
                                 T=T[i * L:(i + 1) * L].copy(),
                                 source="predicted")
                self._tables[key] = tab
                self.stats.table_builds += 1
                self.stats.prefetched_tables += 1
                built += 1
            if tr:
                tracing.end(span)
        if wave is not None:
            tracing.end(wave)
        return built

    def _begin_wave(self) -> tuple:
        self._waves += 1
        return tracing.begin("predict.wave", key=self._waves, annotate=True)

    def _predict(self, target, X: np.ndarray) -> np.ndarray:
        """One regressor over a batch; routes big GBDT batches to Pallas."""
        self.stats.rows_predicted += X.shape[0]
        use = self.use_kernel
        if use == "auto":
            use = (target.gbdt is not None
                   and X.shape[0] >= self.kernel_min_rows
                   and _on_tpu())
        elif use:
            use = target.gbdt is not None
        if use:
            self.stats.kernel_batches += 1
            return self._kernel_predict(target, X)
        if not tracing.ON:
            return target.predict(X)
        span = tracing.begin("predict.numpy")
        out = target.predict(X)
        tracing.end(span)
        return out

    def _kernel_predict(self, target, X: np.ndarray) -> np.ndarray:
        """Leaf indices from the kernel, summed on the host in float64 by
        the model (:func:`repro.kernels.ops.gbdt_predict_model`'s steps,
        taken one by one so that the recorder can time each)."""
        from ..kernels import ops  # lazy: keeps core importable without jax
        g = target.gbdt
        n, n_trees = X.shape[0], g.feats.shape[0]
        n_pad, t_pad = ops.gbdt_padded_shape(n, n_trees)
        self.stats.kernel_cells += n * n_trees
        self.stats.kernel_padded_cells += n_pad * t_pad
        tr = tracing.ON
        if tr:
            span = tracing.begin("predict.stack")
        Xe = target.enc.transform(X) if target.enc is not None else X
        if tr:
            tracing.end(span)
            span = tracing.begin("predict.launch", annotate=True)
        idx = ops.gbdt_leaf_indices(Xe, g.feats, g.thresholds)
        if tr:
            tracing.end(span)
            span = tracing.begin("predict.device_wait", annotate=True)
        idx = np.asarray(idx)
        if tr:
            tracing.end(span)
            span = tracing.begin("predict.leaf_sum")
        raw = np.asarray(g.predict_from_leaves(idx), dtype=np.float64)
        out = target._decode_target(X, raw)
        if tr:
            tracing.end(span)
        return out

    # ------------------------------------------------------------------ #
    #  Point predictions (budget-manager inputs)
    # ------------------------------------------------------------------ #
    def _point_time(self, cache: dict, name: str,
                    device_class: Optional[DeviceClass],
                    which: str) -> float:
        ck = self.register_class(device_class)
        val = cache.get((name, ck))
        if val is None:
            d = self._class_dvfs(ck)
            clock = d.max_clock if which == "min" else d.default_clock
            feats = (self.app_features or {}).get(name)
            if feats is None:
                synth = self._synthesizer
                if synth is None or not synth.knows(name):
                    raise UnknownAppError(name,
                                          known=self.app_features or ())
                # cold apps: evaluate the synthesized roofline at the
                # exact max/default clock (which need not be a ladder
                # element) — same formula every table-driven decision sees
                _, T1 = synth.synthesize(name, (clock,), d)
                val = float(T1[0])
                cache[(name, ck)] = val
                return val
            if ck is not None:
                feats = self.class_features.get(ck, {}).get(name, feats)
            x = np.concatenate([feats, clock_features(clock, d)])
            val = float(self.predictor.predict_time(x[None])[0])
            cache[(name, ck)] = val
            self.stats.point_predictions += 1
        return val

    def t_min(self, name: str,
              device_class: Optional[DeviceClass] = None) -> float:
        """Predicted max-clock ("sprint") time from the app's own profile."""
        return self._point_time(self._tmin, name, device_class, "min")

    def t_dc(self, name: str,
             device_class: Optional[DeviceClass] = None) -> float:
        """Predicted default-clock time from the app's own profile."""
        return self._point_time(self._tdc, name, device_class, "dc")

    # ------------------------------------------------------------------ #
    #  Ground truth (oracle policy)
    # ------------------------------------------------------------------ #
    def _require_testbed(self) -> Testbed:
        if self.testbed is None:
            raise ValueError(
                "PredictionService needs a testbed for ground-truth queries "
                "(oracle policy / truth-based pacing)")
        return self.testbed

    def truth_table(self, app: AppProfile,
                    device_class: Optional[DeviceClass] = None) -> ClockTable:
        # keyed by the (frozen, hashable) profile itself, NOT app.name: a
        # drifted workload reuses the name with shifted coefficients, and
        # the oracle must see the *current* truth (it is an upper bound).
        ck = self.register_class(device_class)
        tab = self._truth.get((app, ck))
        if tab is not None:
            self.stats.truth_hits += 1
            return tab
        tb = self._require_testbed()
        d = None if ck is None else self._classes[ck].dvfs
        clocks = self.clocks_for(ck)
        T = np.array([tb.true_time(app, c, dvfs=d) for c in clocks])
        P = np.array([tb.true_power(app, c, dvfs=d) for c in clocks])
        tab = ClockTable(clocks=clocks, P=P, T=T, source="truth")
        self._truth[(app, ck)] = tab
        self.stats.truth_builds += 1
        return tab

    def true_t_min(self, app: AppProfile,
                   device_class: Optional[DeviceClass] = None) -> float:
        ck = self.register_class(device_class)
        val = self._true_tmin.get((app, ck))
        if val is None:
            d = self._class_dvfs(ck)
            val = self._require_testbed().true_time(
                app, d.max_clock, dvfs=None if ck is None else d)
            self._true_tmin[(app, ck)] = val
        return val

    def true_t_dc(self, app: AppProfile,
                  device_class: Optional[DeviceClass] = None) -> float:
        ck = self.register_class(device_class)
        val = self._true_tdc.get((app, ck))
        if val is None:
            d = self._class_dvfs(ck)
            val = self._require_testbed().true_time(
                app, d.default_clock, dvfs=None if ck is None else d)
            self._true_tdc[(app, ck)] = val
        return val
