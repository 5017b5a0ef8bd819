"""The program's own spans in a traced run, and the metrics read from them.

In a traced run (``run.py --trace 1``) the harness turns the recorder of
``repro.core.tracing`` on from set-up's suite prefetch to the end of the
window and hands its records to :func:`extend`, which attaches them to the
run as ``program_spans`` for the readers of the ``program_span`` metrics
of ``BENCHMARK.json`` (in ``chipbench/metrics/``) and adds two keys to
``breakdown``: ``idle_spans``, the longest idle gaps of the device each
named by the deepest in-program span that covers most of it, and
``clock_skew_us``, how far the recorder's clock, carried onto the trace's
by the window's anchor, lands from the profiler's own annotations of the
same wave spans. :func:`span_cost` adds a third, ``span_cost``: what the
recorder and the benchmark's own wrappers (``chipbench/spans.py``) took
from the window.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s>

is ``run.py`` with ``--trace 1``.
"""
from __future__ import annotations

import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import spans as spans_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

#: Span names the program also enters as profiler annotations
#: (``<name>:<key>``).
ANNOTATED = ("predict.wave", "predict.launch", "predict.device_wait")


class ProgramSpans(spans_mod.Spans):
    """The recorder's spans in the shape of :class:`chipbench.spans.Spans`:
    ``by_layer[span name]`` holds ``(start, end)`` in host-clock seconds,
    ``keys[span name]`` each span's key in the same order, ``parents``
    the names of the spans that enclosed one of ``name``, and ``window``
    the measured window."""

    def __init__(self, records, window: tuple[float, float]):
        super().__init__()
        self.keys: dict[str, list] = {}
        self.parents: dict[str, set[str]] = {}
        self.window = window
        for name, lo, hi, parent, key in sorted(records,
                                                key=lambda r: r[1]):
            self.by_layer.setdefault(name, []).append((lo * 1e-9,
                                                       hi * 1e-9))
            self.keys.setdefault(name, []).append(key)
            outer = self.parents.setdefault(name, set())
            if parent is not None:
                outer.add(parent)

    def depth(self, name: str, _inside: tuple = ()) -> int:
        """The most spans that have enclosed one of ``name``."""
        inside = _inside + (name,)
        return max((1 + self.depth(p, inside)
                    for p in self.parents.get(name, ()) if p not in inside),
                   default=0)

    def in_window(self, name: str) -> list[tuple[float, float]]:
        """The spans of ``name`` that lie wholly inside the window."""
        lo, hi = self.window
        return [(s, e) for s, e in self.by_layer.get(name, ())
                if lo <= s and e <= hi]


def window_share(run, name: str):
    """Percent of the window covered by spans of ``name``; None where the
    run has no recorder spans or none of ``name`` in its window."""
    sp = getattr(run, "program_spans", None)
    if sp is None or not sp.in_window(name):
        return None
    return 100.0 * spans_mod.union_length(sp.in_window(name)) / run.window_s


def idle_spans(planes: list[dict], lo_ns: int, hi_ns: int, offset: float,
               sp: ProgramSpans, idle_gaps: list) -> list:
    """``idle_gaps``' gaps, in its order, each named by the deepest
    recorder span name that covers more than half of it (else the name
    ``idle_gaps`` gives). ``offset`` is the trace clock less the host
    clock, in seconds."""
    out = []
    gaps = trace_mod.idle_gaps(planes, lo_ns, hi_ns)[:len(idle_gaps)]
    for (g_lo, g_hi), (coarse, dur) in zip(gaps, idle_gaps):
        lo, hi = g_lo * 1e-9 - offset, g_hi * 1e-9 - offset
        covering = [n for n, ivs in sp.by_layer.items()
                    if spans_mod.union_length(ivs, lo, hi) > 0.5 * (hi - lo)]
        name = max(covering, key=sp.depth, default=coarse)
        out.append([name, dur])
    return out


def clock_skew_us(planes: list[dict], sp: ProgramSpans, offset: float):
    """The largest distance, in microseconds, between an annotated span's
    start on the recorder's clock carried onto the trace's by ``offset``
    and the start of its annotation in the trace; None without pairs."""
    found: dict[str, list[float]] = {}
    for p in planes:
        for evs in p["lines"].values():
            for n, s, _ in evs:
                if n.partition(":")[0] in ANNOTATED:
                    found.setdefault(n, []).append(s * 1e-9)
    worst = None
    for name in ANNOTATED:
        mine: dict[str, list[float]] = {}
        for (s, _), key in zip(sp.by_layer.get(name, ()),
                               sp.keys.get(name, ())):
            mine.setdefault(f"{name}:{key}", []).append(s + offset)
        for label, starts in mine.items():
            for a, b in zip(sorted(starts), sorted(found.get(label, ()))):
                skew = abs(a - b) * 1e6
                worst = skew if worst is None else max(worst, skew)
    return worst


def extend(breakdown: dict, run, planes: list[dict], records,
           t_window: float) -> None:
    """Attach the recorder's ``records`` to ``run`` as ``program_spans``
    and add ``idle_spans`` and ``clock_skew_us`` to ``breakdown``."""
    sp = ProgramSpans(records, (t_window, t_window + run.window_s))
    run.program_spans = sp
    tr_lo, tr_hi = trace_mod.find_event(planes, "chipbench_traced")
    w_lo, _ = trace_mod.find_event(planes, "chipbench_window")
    offset = w_lo * 1e-9 - t_window
    breakdown["idle_spans"] = idle_spans(planes, tr_lo, tr_hi, offset, sp,
                                         breakdown["idle_gaps"])
    breakdown["clock_skew_us"] = clock_skew_us(planes, sp, offset)


def span_cost(run, n: int = 20_000) -> dict:
    """What the two span systems of a traced run took from its window, as
    an estimate: the spans each recorded inside the window times the host
    time of one, timed here after the window on ``n`` empty spans (a
    recorder span nested in another, as most are; an outermost call
    through a benchmark wrapper). Both systems are on in every traced
    run, so the per-layer shares hold their cost; this says how much."""
    from repro.core import tracing

    lo, hi = run.program_spans.window
    n_rec = sum(lo <= s and e <= hi
                for ivs in run.program_spans.by_layer.values()
                for s, e in ivs)
    n_wrap = sum(lo <= s and e <= hi
                 for layer, ivs in run.spans.by_layer.items()
                 if layer != "engine" for s, e in ivs)
    tracing.enable()
    try:
        outer = tracing.begin("calibrate")
        t0 = time.perf_counter()
        for _ in range(n):
            tracing.end(tracing.begin("calibrate.inner"))
        rec_s = (time.perf_counter() - t0) / n
        tracing.end(outer)
    finally:
        tracing.take()
        tracing.disable()
    call = spans_mod.Spans().wrap(lambda: None, "calibrate", "calibrate")
    t0 = time.perf_counter()
    for _ in range(n):
        call()
    wrap_s = (time.perf_counter() - t0) / n
    return {"recorder_spans": n_rec, "recorder_span_us": rec_s * 1e6,
            "recorder_share": 100.0 * n_rec * rec_s / run.window_s,
            "wrapper_calls": n_wrap, "wrapper_call_us": wrap_s * 1e6,
            "wrapper_share": 100.0 * n_wrap * wrap_s / run.window_s}


def main(argv=None) -> int:
    """``run.py --trace 1`` with the same arguments."""
    from chipbench import run

    args = sys.argv[1:] if argv is None else list(argv)
    return run.main(args + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
