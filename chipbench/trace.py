"""Reduction of a JAX profiler trace to device busy time, kernel time and
the idle gaps of a window.

A trace is read into plain data first (:func:`read_xplane`): a list of
planes, each ``{"name": str, "lines": {line_name: [(name, start_ns,
dur_ns), ...]}}``. Everything after that works on the plain data, so the
tests check the reduction on a constructed trace.
"""
from __future__ import annotations

import glob
import os
import re

from .spans import union_length

#: Device planes of the profiler's trace: one per chip.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: The line of a device plane whose events are the operations that ran.
OPS_LINE = "XLA Ops"
#: An operation event's name is its HLO instruction; keep the name, the op
#: and the result shape, without layouts.
_HLO = re.compile(r"^(%[\w.-]+) = (\S+) ([\w-]+)\(")


def short_name(name: str) -> str:
    """``%gbdt_leaf_indices.1 custom-call s32[1024,512]`` for an HLO
    instruction's text; other names as they are."""
    m = _HLO.match(name)
    if m is None:
        return name
    return f"{m.group(1)} {m.group(3)} {re.sub(r'{[^}]*}', '', m.group(2))}"


def read_xplane(logdir: str) -> list[dict]:
    """Planes, lines and events of the one ``.xplane.pb`` under
    ``logdir``."""
    import jax

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    planes = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def find_event(planes: list[dict], name: str) -> tuple[int, int]:
    """``(start_ns, end_ns)`` of the one event called ``name`` (the
    harness's window annotations)."""
    hits = [(s, s + d) for p in planes for evs in p["lines"].values()
            for n, s, d in evs if n == name]
    if len(hits) != 1:
        raise RuntimeError(f"expected one {name!r} event in the trace, "
                           f"found {len(hits)}")
    return hits[0]


def device_ops(planes: list[dict]) -> list[list[tuple[str, int, int]]]:
    """The operation events of each device plane."""
    return [p["lines"].get(OPS_LINE, []) for p in planes
            if DEVICE_PLANE.match(p["name"])]


def busy_s(planes: list[dict], lo_ns: int, hi_ns: int) -> float:
    """Seconds in ``[lo_ns, hi_ns]`` in which an operation ran, averaged
    over the device planes."""
    per_dev = device_ops(planes)
    if not per_dev:
        raise RuntimeError("the trace holds no device plane")
    return sum(union_length([(s, s + d) for _, s, d in evs], lo_ns, hi_ns)
               for evs in per_dev) / len(per_dev) * 1e-9


def op_seconds(planes: list[dict], lo_ns: int, hi_ns: int,
               match=None) -> dict[str, float]:
    """Device seconds per operation inside ``[lo_ns, hi_ns]``, summed over
    devices and keyed by :func:`short_name`; ``match(short)`` keeps a
    subset."""
    out: dict[str, float] = {}
    for evs in device_ops(planes):
        for n, s, d in evs:
            if s < lo_ns or s + d > hi_ns:
                continue
            n = short_name(n)
            if match is None or match(n):
                out[n] = out.get(n, 0.0) + d * 1e-9
    return out


def idle_gaps(planes: list[dict], lo_ns: int, hi_ns: int
              ) -> list[tuple[int, int]]:
    """Intervals in ``[lo_ns, hi_ns]`` in which no operation ran on the
    first device, longest first."""
    per_dev = device_ops(planes)
    evs = sorted((s, s + d) for _, s, d in (per_dev[0] if per_dev else ()))
    gaps, cur = [], lo_ns
    for s, e in evs:
        if e <= lo_ns or s >= hi_ns:
            continue
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi_ns > cur:
        gaps.append((cur, hi_ns))
    return sorted(gaps, key=lambda g: g[0] - g[1])
