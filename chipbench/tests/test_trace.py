"""The reduction from a profiler trace to busy, idle and kernel time, on a
constructed trace."""
import pytest

from chipbench import harness, spans, trace


def constructed():
    ms = 1_000_000
    kernel = ("%gbdt_leaf_indices.1 = s32[1024,512]{1,0:T(8,128)} "
              "custom-call(f32[1024,23]{1,0:T(8,128)S(1)} %copy), "
              "custom_call_target=\"tpu_custom_call\"")
    ops = [("fusion.1", 10 * ms, 5 * ms), (kernel, 12 * ms, 6 * ms),
           ("fusion.2", 40 * ms, 10 * ms), ("fusion.3", 95 * ms, 10 * ms)]
    return [
        {"name": "/host:CPU", "lines": {"python": [
            ("chipbench_traced", 0, 100 * ms),
            ("chipbench_window", 30 * ms, 70 * ms)]}},
        {"name": "/device:TPU:0", "lines": {trace.OPS_LINE: ops}},
        {"name": "/device:TPU:0 SparseCore 0", "lines": {
            trace.OPS_LINE: [("other", 0, 100 * ms)]}},
    ]


def test_annotations():
    planes = constructed()
    assert trace.find_event(planes, "chipbench_window") == (30e6, 100e6)
    with pytest.raises(RuntimeError):
        trace.find_event(planes, "missing")


def test_busy_is_the_union_of_ops_in_the_window():
    planes = constructed()
    # [10,18] + [40,50] + [95,100] clipped to [0,100] ms
    assert trace.busy_s(planes, 0, 100_000_000) == pytest.approx(0.023)
    assert trace.busy_s(planes, 30_000_000, 100_000_000) == pytest.approx(
        0.015)


def test_kernel_seconds_by_short_name():
    planes = constructed()
    kern = trace.op_seconds(planes, 0, 100_000_000, harness.is_kernel)
    assert kern == {"%gbdt_leaf_indices.1 custom-call s32[1024,512]":
                    pytest.approx(0.006)}
    # the window [30, 100] ms holds no kernel
    assert trace.op_seconds(planes, 30_000_000, 100_000_000,
                            harness.is_kernel) == {}
    every = trace.op_seconds(planes, 0, 100_000_000)
    assert every["fusion.2"] == pytest.approx(0.010)
    # fusion.3 runs past the window's end and is left out
    assert set(every) == {"fusion.1", "fusion.2",
                          "%gbdt_leaf_indices.1 custom-call s32[1024,512]"}


def test_idle_gaps_longest_first():
    gaps = trace.idle_gaps(constructed(), 0, 100_000_000)
    ms = 1_000_000
    assert gaps == [(50 * ms, 95 * ms), (18 * ms, 40 * ms), (0, 10 * ms)]


def test_union_length():
    assert spans.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert spans.union_length([(0, 2), (1, 3), (5, 6)], 2, 5.5) == 1.5
    assert spans.union_length([]) == 0
