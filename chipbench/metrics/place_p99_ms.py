"""99th percentile of the wall time from admission to dispatch, over every
job dispatched in the window: what a stall of the control plane costs the
jobs queued behind it."""
import numpy as np


def read(run):
    if run.latencies_s.size == 0:
        return None
    return float(np.percentile(run.latencies_s, 99)) * 1e3
