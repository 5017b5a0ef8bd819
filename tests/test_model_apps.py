"""Derivation battery for the model-derived app suite (PR 10).

Four nets over :mod:`repro.core.model_apps`:

* **Counter fidelity** — every registered architecture's derived
  ``flops`` match an independent recomputation from the
  :mod:`repro.roofline.analysis` analytic terms (``model_flops`` +
  ``ssm_scan_correction``) at the derivation shapes, for all three
  phases; per-chip magnitudes sit under the paper-suite band caps.
* **Phase physics** — decode apps have lower arithmetic intensity than
  prefill for the same arch (and sit on the memory-bound side of the
  device ridge point, while prefill sits compute-bound); train apps are
  the only ones carrying collective bytes.
* **Ladder shape** (hypothesis property) — every derived app yields
  finite, positive, core-monotone-per-mem-block synthesized (P, T)
  ladders on all stock ``DEVICE_CLASSES`` (the same property the
  cold-start suite pins for random counters, now for the derived ones);
  truth ladders stay finite and positive everywhere.
* **Determinism + inert registration** — same call → bit-identical
  ``AppProfile``\\ s; seeds are unique and disjoint from the paper
  suite's block; :func:`register_model_apps` never touches the shared
  testbed RNG stream, never perturbs cached paper-app tables, and makes
  derived apps first-class citizens of the service (profiled tier).
"""
from __future__ import annotations

import copy
import itertools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # not installed in this container — deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.configs import _ARCH_IDS, get_config
from repro.configs.paper_suite import PAPER_APPS
from repro.core import (ColdStartSynthesizer, DEVICE_CLASSES,
                        EnergyTimePredictor, PredictionService,
                        PredictorConfig, Testbed, UnknownAppError, V5E_DVFS,
                        build_dataset, profile_features)
from repro.core.model_apps import (DECODE_STEPS, KIND_KNOBS, PHASES,
                                   chips_for, derive_app, derive_counters,
                                   kernel_apps, model_app_suite,
                                   phase_shape, register_model_apps)
from repro.roofline.analysis import model_flops, ssm_scan_correction

SUITE = model_app_suite()
BY_NAME = {a.name: a for a in SUITE}
_FLOP_CAP, _BYTE_CAP = 3.0e14, 1.2e12


def _expected_flops(arch: str, phase: str, n_chips: int) -> float:
    """Independent recomputation from the analysis-module primitives."""
    cfg = get_config(arch)
    shape = phase_shape(phase)
    flops = model_flops(cfg, shape, n_chips)
    flops += ssm_scan_correction(cfg, shape, n_chips)[0]
    if phase == "decode":
        flops *= DECODE_STEPS
    return flops


# ---------------------------------------------------------------------- #
#  Counter fidelity vs roofline/analysis.py
# ---------------------------------------------------------------------- #
class TestDerivedCounters:
    @pytest.mark.parametrize("arch", _ARCH_IDS)
    def test_flops_match_analysis_terms(self, arch):
        """Derived per-chip FLOPs == the analytic 6·N·D / 2·N·D terms
        (plus the SSM scan correction) at the derivation shapes — for
        every registered architecture and every phase."""
        for phase in PHASES:
            app = BY_NAME[f"{arch}:{phase}"]
            want = _expected_flops(arch, phase, app.n_chips)
            assert app.flops == pytest.approx(want, rel=1e-9), phase

    @pytest.mark.parametrize("arch", _ARCH_IDS)
    def test_counters_positive_and_under_band_caps(self, arch):
        """chips_for keeps per-chip magnitudes inside the paper-suite
        band: positive, FLOPs <= 3e14, HBM bytes <= 1.2e12."""
        for phase in PHASES:
            app = BY_NAME[f"{arch}:{phase}"]
            assert app.flops > 0 and app.hbm_bytes > 0
            assert app.flops <= _FLOP_CAP * (1 + 1e-12)
            assert app.hbm_bytes <= _BYTE_CAP * (1 + 1e-12)
            assert app.n_chips == chips_for(get_config(arch), phase)
            assert app.n_chips & (app.n_chips - 1) == 0   # power of two

    def test_decode_counters_scale_with_generation_segment(self):
        """A decode app is a DECODE_STEPS-token segment: counters are
        exactly DECODE_STEPS x the single-step derivation."""
        cfg = get_config("qwen2_5_14b")
        n = chips_for(cfg, "decode")
        one = derive_counters(cfg, "decode", n_chips=n)
        assert one["flops"] == pytest.approx(
            model_flops(cfg, phase_shape("decode"), n) * DECODE_STEPS,
            rel=1e-9)

    def test_aot_counters_from_compiled_decode_step(self):
        """A compiled decode step's cost analysis yields per-chip
        (flops, bytes), and derive_counters takes them over the analytic
        terms when given the artifact."""
        import jax
        import jax.numpy as jnp
        from repro.configs.base import reduce_for_smoke
        from repro.core.model_apps import aot_counters
        from repro.models import model
        from repro.train.serve import make_serve_step
        cfg = reduce_for_smoke(get_config("smollm_360m"))
        params = jax.eval_shape(lambda: model.init(cfg,
                                                   jax.random.PRNGKey(0)))
        cache = jax.eval_shape(lambda: model.init_cache(cfg, 2, 16))
        tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        compiled = jax.jit(make_serve_step(cfg)).lower(
            params, cache, tok, pos).compile()
        got = aot_counters(compiled, n_chips=2)
        assert got is not None
        flops, nbytes = got
        assert flops > 0 and nbytes > 0
        refined = derive_counters(cfg, "decode", n_chips=2,
                                  compiled=compiled)
        assert (refined["flops"], refined["hbm_bytes"]) == (flops, nbytes)

    def test_train_apps_carry_collectives(self):
        """Train steps are collective-heavy: every train app ships
        gradient all-reduce bytes over >= 2 chips; serving phases ship
        none (decode/prefill are single-slice dispatches)."""
        for arch in _ARCH_IDS:
            assert BY_NAME[f"{arch}:train_step"].coll_bytes > 0, arch
            assert BY_NAME[f"{arch}:train_step"].n_chips >= 2, arch
            assert BY_NAME[f"{arch}:prefill"].coll_bytes == 0.0, arch
            assert BY_NAME[f"{arch}:decode"].coll_bytes == 0.0, arch

    def test_ssm_scan_correction_is_included(self):
        """SSM-family prefill FLOPs strictly exceed the bare analytic
        model term — the scan-recurrence correction is in the counters."""
        for arch in ("falcon_mamba_7b", "zamba2_7b"):
            cfg = get_config(arch)
            app = BY_NAME[f"{arch}:prefill"]
            bare = model_flops(cfg, phase_shape("prefill"), app.n_chips)
            assert app.flops > bare
            extra = ssm_scan_correction(cfg, phase_shape("prefill"),
                                        app.n_chips)[0]
            assert app.flops == pytest.approx(bare + extra, rel=1e-9)

    def test_kind_knobs_applied_per_phase(self):
        """Every derived app carries its kind's latent-knob row (decode:
        stall-prone; train: extra overhead), and MoE archs are spiky in
        every phase while non-MoE LM archs are not."""
        for arch in _ARCH_IDS:
            for phase in PHASES:
                app = BY_NAME[f"{arch}:{phase}"]
                kind = "train" if phase == "train_step" else phase
                assert app.kind == kind
                knobs = KIND_KNOBS[kind]
                assert app.stall_frac == knobs["stall_frac"]
                assert app.overhead_s == knobs["overhead_s"]
                if get_config(arch).family == "moe":
                    assert app.spike > 0, (arch, phase)
                else:
                    assert app.spike == knobs["spike"], (arch, phase)

    def test_kernel_apps_present_and_shaped(self):
        names = {a.name for a in kernel_apps()}
        assert names == {"flash_attention", "mamba_scan", "moe_dispatch"}
        fa, ms, md = kernel_apps()
        assert fa.arithmetic_intensity > 1000        # compute-bound
        assert ms.arithmetic_intensity < 50          # memory-bound scan
        assert ms.stall_frac > fa.stall_frac         # recurrence stalls
        assert md.spike > 0 and md.coll_bytes > 0    # spiky, all-to-all
        for a in (fa, ms, md):
            assert a.kind == "kernel" and a.name in BY_NAME


# ---------------------------------------------------------------------- #
#  Phase physics: decode memory-bound, prefill compute-bound
# ---------------------------------------------------------------------- #
class TestArithmeticIntensity:
    @pytest.mark.parametrize("arch", _ARCH_IDS)
    def test_decode_ai_below_prefill(self, arch):
        dec = BY_NAME[f"{arch}:decode"]
        pre = BY_NAME[f"{arch}:prefill"]
        assert dec.arithmetic_intensity < pre.arithmetic_intensity

    @pytest.mark.parametrize("arch", _ARCH_IDS)
    def test_phases_straddle_the_ridge_point(self, arch):
        """Decode sits on the memory-bound side of every stock device's
        ridge point (peak_flops / hbm_bw), prefill on the compute-bound
        side — the derivation's memory-vs-compute contract holds on all
        DEVICE_CLASSES, not just the default chip."""
        dec = BY_NAME[f"{arch}:decode"]
        pre = BY_NAME[f"{arch}:prefill"]
        for cls in DEVICE_CLASSES.values():
            ridge = cls.dvfs.peak_flops / cls.dvfs.hbm_bw
            assert dec.arithmetic_intensity < ridge, cls.name
            assert pre.arithmetic_intensity > ridge, cls.name

    def test_decode_time_dominated_by_memory(self):
        """At the default clock the decode apps' memory term dominates
        their compute term (the stall-prone, memory-bound serving
        regime the latent knobs encode)."""
        d = V5E_DVFS
        for arch in _ARCH_IDS:
            app = BY_NAME[f"{arch}:decode"]
            t_mem = app.hbm_bytes / (d.hbm_bw * d.default_clock.s_mem
                                     * app.mem_eff)
            t_cmp = app.flops / (d.peak_flops * d.default_clock.s_core
                                 * app.core_eff)
            assert t_mem > t_cmp, arch


# ---------------------------------------------------------------------- #
#  Ladder shape on every stock DeviceClass (hypothesis property)
# ---------------------------------------------------------------------- #
class TestDerivedLadderShape:
    @settings(max_examples=20, deadline=None)
    @given(idx=st.integers(0, len(SUITE) - 1))
    def test_synthesized_finite_positive_core_monotone(self, idx):
        """Every derived app's static counters synthesize to finite,
        positive (P, T) ladders with T monotone non-increasing in core
        clock at fixed mem clock, on every stock device class — the
        cold-start tier serves derivation output soundly."""
        app = SUITE[idx]
        synth = ColdStartSynthesizer(dvfs=V5E_DVFS)
        synth.register(app)
        for cls in DEVICE_CLASSES.values():
            d = cls.dvfs
            clocks = d.clock_list()
            P, T = synth.synthesize(app.name, clocks, d)
            assert np.all(np.isfinite(P)) and np.all(np.isfinite(T))
            assert np.all(P > 0) and np.all(T > 0)
            for s_mem, group in itertools.groupby(
                    zip(clocks, T), key=lambda ct: ct[0].s_mem):
                ladder = [t for _, t in group]  # core-ascending per block
                for lo, hi in zip(ladder, ladder[1:]):
                    assert hi <= lo * (1.0 + 1e-9), (cls.name, s_mem)

    @settings(max_examples=15, deadline=None)
    @given(idx=st.integers(0, len(SUITE) - 1))
    def test_truth_ladder_finite_positive_everywhere(self, idx):
        """The simulator's ground truth stays finite and positive for
        every derived app on every class's full clock grid — wiggles,
        spikes, and stalls included."""
        app = SUITE[idx]
        tb = Testbed(seed=0)
        for cls in DEVICE_CLASSES.values():
            for clock in cls.dvfs.clock_list():
                t = tb.true_time(app, clock, dvfs=cls.dvfs)
                p = tb.true_power(app, clock, dvfs=cls.dvfs)
                assert np.isfinite(t) and t > 0, (cls.name, clock)
                assert np.isfinite(p) and p > 0, (cls.name, clock)


# ---------------------------------------------------------------------- #
#  Determinism + observationally inert registration
# ---------------------------------------------------------------------- #
class TestRegistryDeterminism:
    def test_suite_bit_identical_across_calls(self):
        a, b = model_app_suite(), model_app_suite()
        assert a == b                       # frozen-dataclass equality
        for x, y in zip(a, b):
            for f in ("flops", "hbm_bytes", "coll_bytes", "seed",
                      "stall_frac", "wiggle_time", "spike", "n_chips"):
                assert getattr(x, f) == getattr(y, f), (x.name, f)

    def test_derive_app_accepts_cli_aliases(self):
        assert derive_app("qwen2.5-14b", "decode") == \
            derive_app("qwen2_5_14b", "decode")

    def test_names_unique_and_seeds_disjoint_from_paper_suite(self):
        names = [a.name for a in SUITE]
        assert len(names) == len(set(names))
        assert len(SUITE) == 3 * len(_ARCH_IDS) + 3
        seeds = [a.seed for a in SUITE]
        assert len(seeds) == len(set(seeds))
        paper_seeds = {a.seed for a in PAPER_APPS}
        assert not paper_seeds & set(seeds)
        assert not {a.name for a in PAPER_APPS} & set(names)

    def test_feature_vectors_deterministic(self):
        tb = Testbed(seed=0)
        f1 = register_model_apps(None, tb)
        f2 = register_model_apps(None, tb)
        assert sorted(f1) == sorted(f2)
        for name in f1:
            assert np.array_equal(f1[name], f2[name]), name


class TestInertRegistration:
    def _service(self):
        tb = Testbed(seed=0)
        X, yp, yt, _ = build_dataset(PAPER_APPS, tb, seed=0)
        rng = np.random.default_rng(7)
        feats = {a.name: profile_features(a, tb, rng=rng)
                 for a in PAPER_APPS}
        pred = EnergyTimePredictor(PredictorConfig()).fit(X, yp, yt)
        return tb, PredictionService(V5E_DVFS, predictor=pred,
                                     app_features=feats, testbed=tb)

    def test_shared_rng_stream_untouched(self):
        """Registration profiles with dedicated per-app generators: the
        testbed's shared stream (the engine's determinism backbone) is
        bit-identical before and after."""
        tb = Testbed(seed=42)
        state = copy.deepcopy(tb._rng.bit_generator.state)
        register_model_apps(None, tb)
        assert tb._rng.bit_generator.state == state

    def test_paper_tables_and_epoch_unperturbed(self):
        """Cached paper-app ladders are byte-identical across a
        registration, and the service's cache epoch never bumps —
        invariant 12's service-level face."""
        tb, svc = self._service()
        before = {a.name: svc.base_table(a.name) for a in PAPER_APPS[:4]}
        epoch = svc._epoch
        register_model_apps(svc, tb)
        assert svc._epoch == epoch
        for name, tab in before.items():
            after = svc.base_table(name)
            assert after is tab or (
                np.array_equal(after.P, tab.P)
                and np.array_equal(after.T, tab.T))

    def test_registered_apps_are_first_class(self):
        """Before registration a derived app is unknown; after, it
        resolves through the profiled tier (note_app returns False — no
        cold-start needed) with a finite positive ladder."""
        tb, svc = self._service()
        app = derive_app("mixtral_8x22b", "decode")
        with pytest.raises(UnknownAppError):
            svc.base_table(app.name)
        register_model_apps(svc, tb)
        assert svc.note_app(app) is False      # profiled-tier no-op
        tab = svc.base_table(app.name)
        assert np.all(np.isfinite(tab.P)) and np.all(tab.P > 0)
        assert np.all(np.isfinite(tab.T)) and np.all(tab.T > 0)

    def test_register_is_idempotent_and_non_clobbering(self):
        tb, svc = self._service()
        first = register_model_apps(svc, tb)
        held = {n: svc.app_features[n] for n in first}
        register_model_apps(svc, tb)
        for n in first:
            assert svc.app_features[n] is held[n], n
