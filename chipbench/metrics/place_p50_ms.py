"""Median wall time from the start of a job's wait to its dispatch, over
every job dispatched in the window; its wave's table builds are inside it.
The wait starts at the job's enqueue, or at its first admission check
where admission control runs, so a parked job's time parked counts."""
import numpy as np


def read(run):
    if run.latencies_s.size == 0:
        return None
    return float(np.percentile(run.latencies_s, 50)) * 1e3
