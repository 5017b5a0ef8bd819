"""A frozen copy of the simulated fleet's physics: the noiseless time and
power of one app at one clock on one device class.

This is what the devices of the deployment do, the world the scheduler
acts in, not the scheduler. It is copied from ``repro.core.simulator``
(``Testbed.true_time``, ``true_power``, ``_wiggle``) and
``repro.core.dvfs`` (``DVFSConfig.power`` and its voltage curves) as they
stood when the benchmark was written, so that a later change of the program
cannot move the yardstick. A device class's ladder and electrical constants
are read from its configuration as data.
"""
from __future__ import annotations

import numpy as np


def _wiggle(seed: int, amp: float, x: float, y: float,
            n_terms: int = 4) -> float:
    """Smooth seeded 2D pseudo-random function in [-amp, amp]."""
    if amp <= 0:
        return 0.0
    rng = np.random.default_rng(seed)
    ks = rng.uniform(0.5, 3.0, size=(n_terms, 2))
    phase = rng.uniform(0, 2 * np.pi, size=n_terms)
    w = rng.normal(size=n_terms)
    w /= np.sqrt((w ** 2).sum()) + 1e-12
    val = float(np.sum(w * np.sin(2 * np.pi * (ks[:, 0] * x + ks[:, 1] * y)
                                  + phase)))
    return amp * val / np.sqrt(2)


def chip_power(d, clock, u_core: float, u_mem: float) -> float:
    """Chip draw (W) of DVFS configuration ``d`` at ``clock`` and the two
    domains' utilizations: static power plus V^2 f per domain, gated by
    utilization."""
    vc = max(d.v_floor, 0.45 + d.v_slope * clock.s_core)
    vm = max(0.80, 0.60 + 0.40 * clock.s_mem)
    g_c = d.idle_core_frac + (1 - d.idle_core_frac) * float(
        np.clip(u_core, 0, 1))
    g_m = d.idle_mem_frac + (1 - d.idle_mem_frac) * float(
        np.clip(u_mem, 0, 1))
    return (d.p_static + d.a_core * vc * vc * clock.s_core * g_c
            + d.a_mem * vm * vm * clock.s_mem * g_m)


def true_time(app, clock, d) -> float:
    """Noiseless run time (s) of ``app`` at ``clock`` on configuration
    ``d``."""
    flops_rate = d.peak_flops * clock.s_core * app.core_eff
    t_compute = (1 - app.stall_frac) * app.flops / flops_rate + (
        app.stall_frac * app.flops / (d.peak_flops * app.core_eff))
    t_mem = app.hbm_bytes / (d.hbm_bw * clock.s_mem * app.mem_eff)
    t_coll = app.coll_bytes / d.ici_bw
    p = 8.0
    terms = np.array([t_compute, t_mem, t_coll, 1e-12])
    t_base = float((terms ** p).sum() ** (1.0 / p))
    w = _wiggle(app.seed * 7919 + 13, app.wiggle_time, clock.s_core,
                clock.s_mem)
    s = 0.0
    if app.spike > 0:
        rng = np.random.default_rng(app.seed * 104729 + 3)
        c = rng.uniform(0.5, 1.05)
        width = rng.uniform(0.03, 0.08)
        s = app.spike * float(np.exp(-((clock.s_core - c) ** 2)
                                     / (2 * width ** 2)))
    return t_base * (1.0 + w + s) + app.overhead_s


def true_power(app, clock, d) -> float:
    """Noiseless draw (W) of ``app`` at ``clock`` on configuration ``d``."""
    t = true_time(app, clock, d)
    t_core = app.flops / (d.peak_flops * clock.s_core * app.core_eff)
    t_mem = app.hbm_bytes / (d.hbm_bw * clock.s_mem * app.mem_eff)
    u_core = min(t_core / max(t, 1e-12), 1.0)
    u_mem = min(t_mem / max(t, 1e-12), 1.0)
    w = _wiggle(app.seed * 15485863 + 29, app.wiggle_power, clock.s_core,
                clock.s_mem)
    return chip_power(d, clock, u_core, u_mem) * (1.0 + w)


def peak_power(d) -> float:
    """A chip's most draw: the top clocks with both domains busy."""
    top = type(d.default_clock)(max(d.core_scales), max(d.mem_scales))
    return chip_power(d, top, 1.0, 1.0)
