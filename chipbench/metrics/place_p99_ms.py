"""99th percentile of the wall time from the start of a job's wait to its
dispatch, over every job dispatched in the window (the wait as
``place_p50_ms`` times it): what a stall of the control plane costs the
jobs queued behind it."""
import numpy as np


def read(run):
    if run.latencies_s.size == 0:
        return None
    return float(np.percentile(run.latencies_s, 99)) * 1e3
