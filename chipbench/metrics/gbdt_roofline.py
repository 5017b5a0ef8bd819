"""The GBDT kernel's share of its roofline in the window: the least time
the chip could take for the calls' work, over the kernel's device time.

The work is counted from each call's shapes by the algorithm, not by the
kernel's implementation: X read (n x F x 4 B), each tree's features and
thresholds read (T x D x 8 B), leaf indices written (n x T x 4 B), and
n x T x D comparisons. The least time is the larger of operations over the
peak rate and bytes over the peak bandwidth; :func:`bound` says which.
"""


def work(n: int, n_feat: int, n_trees: int, depth: int) -> tuple[int, int]:
    """(operations, bytes) of one call."""
    ops = n * n_trees * depth
    nbytes = n * n_feat * 4 + n_trees * depth * 8 + n * n_trees * 4
    return ops, nbytes


def least_time(calls, peaks: dict) -> tuple[float, float]:
    """(compute-bound, memory-bound) least seconds of all calls."""
    ops = sum(work(*c)[0] for c in calls)
    nbytes = sum(work(*c)[1] for c in calls)
    return ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]


def bound(calls, peaks: dict) -> str:
    t_ops, t_bytes = least_time(calls, peaks)
    return "memory" if t_bytes >= t_ops else "compute"


def read(run):
    if not run.kernel_calls or run.kernel_s <= 0.0:
        return None
    return 100.0 * max(least_time(run.kernel_calls, run.peaks)) / run.kernel_s
