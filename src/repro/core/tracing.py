"""The program's own span recorder, off by default.

    from repro.core import tracing
    tracing.enable()
    result = run_schedule(...)
    spans = tracing.take()        # [(name, start_ns, end_ns, parent, key)]
    tracing.disable()

A span is one interval of one layer boundary (the table in
docs/architecture.md, "Tracing"), on ``time.perf_counter_ns``. ``parent``
is the name of the enclosing span: the innermost span open when it began,
or the one a site names (a job's decision inside the job's wait). ``key``
identifies one job (its id) or one wave (its number); a span given no key
takes its enclosing span's, so every span of one job or one wave carries
the same identifier.

Off, a site costs one test of :data:`ON`: no clock is read and nothing is
allocated. Recording never changes what the program computes, only which
intervals are appended here. A span whose block raises is not recorded.
"""
from __future__ import annotations

import time
from typing import Optional

__all__ = ["ON", "enable", "disable", "take", "begin", "end", "record",
           "clock"]

#: Whether sites record; test it, never assign it (use :func:`enable`).
ON = False
#: The recorder's clock, in integer nanoseconds.
clock = time.perf_counter_ns

_spans: list[tuple] = []
#: ``(name, key)`` of each span begun and not yet ended, innermost last
_open: list[tuple[str, object]] = []


def enable() -> None:
    global ON
    ON = True


def disable() -> None:
    global ON
    ON = False


def take() -> list[tuple[str, int, int, Optional[str], object]]:
    """Every span recorded since the last call, in the order they ended;
    the recorder keeps none of them."""
    global _spans
    out, _spans = _spans, []
    _open.clear()
    return out


def begin(name: str, key=None, parent: Optional[str] = None,
          annotate: bool = False) -> tuple:
    """Open a span; pass the token to :func:`end`. ``annotate`` also
    enters it as a ``jax.profiler.TraceAnnotation`` named
    ``<name>:<key>``, so that it appears on the profiler's host plane."""
    if _open:
        top_name, top_key = _open[-1]
        parent = parent or top_name
        key = top_key if key is None else key
    ann = None
    if annotate:
        import jax
        ann = jax.profiler.TraceAnnotation(f"{name}:{key}")
    depth = len(_open)
    _open.append((name, key))
    start = clock()
    if ann is not None:
        ann.__enter__()
    return name, start, parent, key, depth, ann


def end(token: tuple) -> int:
    """Close the span ``token`` opened, and any left open inside it;
    returns the clock at its end."""
    name, start, parent, key, depth, ann = token
    stop = clock()
    if ann is not None:
        ann.__exit__(None, None, None)
    del _open[depth:]
    _spans.append((name, start, stop, parent, key))
    return stop


def record(name: str, start: int, stop: int, parent: Optional[str] = None,
           key=None) -> None:
    """Append a span timed by the caller (one that does not nest, such as
    a job's wait, which overlaps other jobs' waits)."""
    _spans.append((name, start, stop, parent, key))
