"""A traced run of one cell with the program's own span recorder on.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s>

From the root of a checkout. The run is ``run.py --trace 1``'s, with the
recorder of ``repro.core.tracing`` on from set-up's suite prefetch to the
end of the window. Its result line adds the in-program metrics of
:data:`METRICS` (readers in ``chipbench/metrics/``) and two keys to
``breakdown``: ``idle_spans``, the longest idle gaps of the device each
named by the deepest in-program span that covers most of it, and
``clock_skew_us``, how far the recorder's clock, carried onto the trace's
by the window's anchor, lands from the profiler's own annotations of the
same wave spans. The benchmark's own runs do not turn the recorder on; the
harness would take this in by enabling the recorder before the suite
prefetch in ``run_cell`` and calling :func:`extend` from
``_reduce_trace``.
"""
from __future__ import annotations

import contextlib
import json
import pathlib
import sys
import time

T_START = time.perf_counter()

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import spans as spans_mod  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

#: Span names the program also enters as profiler annotations
#: (``<name>:<key>``).
ANNOTATED = ("predict.wave", "predict.launch", "predict.device_wait")
_WAVE = ["fed64-novel"]
_ALL = ["fed64-novel", "fed64-recur", "pod256-recur"]
#: The in-program metrics, as ``BENCHMARK.json`` would list them.
METRICS = [
    {"name": "wave_stack_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": "prediction service",
     "moves": "jobs_per_s", "workloads": _WAVE},
    {"name": "kernel_launch_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": "kernel", "moves": "jobs_per_s",
     "workloads": _WAVE},
    {"name": "kernel_wait_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": "kernel", "moves": "jobs_per_s",
     "workloads": _WAVE},
    {"name": "leaf_sum_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": "prediction service",
     "moves": "jobs_per_s", "workloads": _WAVE},
    {"name": "place_wait_predict_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": "engine", "moves": "place_p99_ms",
     "workloads": _WAVE},
    {"name": "rack_advance_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": "facility coordinator",
     "moves": "jobs_per_s", "workloads": _ALL},
    {"name": "rebalance_share", "unit": "%", "better": "lower",
     "source": "program_span", "layer": "facility coordinator",
     "moves": "jobs_per_s", "workloads": _ALL},
]


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


@contextlib.contextmanager
def recorder_on(harness):
    """``harness.run_cell`` with the recorder on from the suite prefetch
    (right after the kernel shapes are warmed) until the trace is
    reduced, whose result :func:`extend` completes."""
    from repro.core import tracing

    warm, reduce = harness.warm_kernel_shapes, harness._reduce_trace

    def warm_then_record(*args):
        warm(*args)
        tracing.take()
        tracing.enable()

    def reduce_and_extend(run, logdir, t_window):
        records = tracing.take()
        tracing.disable()
        out = reduce(run, logdir, t_window)
        extend(out, run, trace_mod.read_xplane(logdir), records, t_window)
        return out

    harness.warm_kernel_shapes = warm_then_record
    harness._reduce_trace = reduce_and_extend
    try:
        yield
    finally:
        harness.warm_kernel_shapes, harness._reduce_trace = warm, reduce
        tracing.disable()
        tracing.take()


def run_cell(cell: dict, seed: int, seconds: float, t_start: float,
             check_chips: bool = True) -> dict:
    """One traced run of ``cell`` with the recorder on; the result object
    of ``run.py --trace 1`` with the in-program metrics and keys."""
    from chipbench import harness

    cell = dict(cell, per_layer=cell["per_layer"] + [
        m for m in METRICS if cell["name"] in m["workloads"]])
    with recorder_on(harness):
        return harness.run_cell(cell, seed, seconds, True, t_start,
                                check_chips=check_chips)


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    a = p.parse_args(argv)

    import jax

    from chipbench import harness
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(a.workload)
    try:
        harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    out = run_cell(cell, a.seed, a.seconds, T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
