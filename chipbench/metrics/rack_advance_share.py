"""Share of the window in ``coord.rack_advance``: every rack's ``advance``
and the facility's live-grant filter (the program's recorder)."""
from chipbench.program_trace import window_share


def read(run):
    return window_share(run, "coord.rack_advance")
