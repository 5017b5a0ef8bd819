"""Share of the window in ``predict.stack``: building a wave's rows and
encoding them (the program's recorder; see ``chipbench/program_trace.py``)."""
from chipbench.program_trace import window_share


def read(run):
    return window_share(run, "predict.stack")
