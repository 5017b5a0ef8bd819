"""Share of the window in ``coord.rebalance``: the facility's re-split of
free headroom across racks on every decision (the program's recorder)."""
from chipbench.program_trace import window_share


def read(run):
    return window_share(run, "coord.rebalance")
