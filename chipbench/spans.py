"""Spans the benchmark records around the public calls into each layer.

Installed only for a traced run (``--trace 1``): a wrapper times every call
into a layer with the host clock, keeps the outermost call of each layer
(``table`` calls ``base_table``; only the outer one counts), and keeps the
intervals in memory. Nothing in the program is edited; the wrappers are set
on the objects the run builds, or on a class for the window's length.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Iterator

import numpy as np


class Spans:
    """Per-layer lists of ``(start, end)`` host-clock intervals."""

    def __init__(self):
        self.by_layer: dict[str, list[tuple[float, float]]] = {}
        self.by_call: dict[str, list[float]] = {}
        self._depth: dict[str, int] = {}

    def wrap(self, fn: Callable, layer: str, call: str) -> Callable:
        """``fn`` timed as a call into ``layer``; only the outermost call
        of a layer is recorded. ``call`` names the method, so that one
        method's durations can be read alone."""
        spans = self.by_layer.setdefault(layer, [])
        durations = self.by_call.setdefault(call, [])
        depth = self._depth
        depth.setdefault(layer, 0)

        @functools.wraps(fn)
        def timed(*args, **kw):
            if depth[layer]:
                return fn(*args, **kw)
            depth[layer] += 1
            t0 = time.perf_counter()
            try:
                return fn(*args, **kw)
            finally:
                t1 = time.perf_counter()
                depth[layer] -= 1
                spans.append((t0, t1))
                durations.append(t1 - t0)
        return timed

    def wrap_methods(self, obj, names, layer: str) -> None:
        """Time the named methods of one object, as instance attributes."""
        for name in names:
            setattr(obj, name, self.wrap(getattr(obj, name), layer,
                                         f"{layer}.{name}"))

    def iterate(self, it: Iterator, layer: str) -> Iterator:
        """``it`` with each ``next()`` timed as a call into ``layer``."""
        nxt = self.wrap(it.__next__, layer, f"{layer}.next")
        while True:
            try:
                yield nxt()
            except StopIteration:
                return

    def total(self, layer: str) -> float:
        return float(sum(e - s for s, e in self.by_layer.get(layer, ())))


def union_length(intervals, lo: float = -np.inf, hi: float = np.inf) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
