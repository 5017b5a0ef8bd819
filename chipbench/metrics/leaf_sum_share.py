"""Share of the window in ``predict.leaf_sum``: the float64 sum of the
selected leaves and the target's decoding (the program's recorder)."""
from chipbench.program_trace import window_share


def read(run):
    return window_share(run, "predict.leaf_sum")
