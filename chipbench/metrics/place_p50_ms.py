"""Median wall time from a job's admission to its dispatch, over every job
dispatched in the window; its wave's table builds are inside it."""
import numpy as np


def read(run):
    if run.latencies_s.size == 0:
        return None
    return float(np.percentile(run.latencies_s, 50)) * 1e3
