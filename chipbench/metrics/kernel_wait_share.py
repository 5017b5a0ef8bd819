"""Share of the window in ``predict.device_wait``: copying the kernel's
leaf indices to the host, the device's compute included (the program's
recorder)."""
from chipbench.program_trace import window_share


def read(run):
    return window_share(run, "predict.device_wait")
