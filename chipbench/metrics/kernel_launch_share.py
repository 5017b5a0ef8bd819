"""Share of the window in ``predict.launch``: the kernel call until its
device array is returned (conversion, padding, one-hot, transfer to the
device, dispatch; the program's recorder)."""
from chipbench.program_trace import window_share


def read(run):
    return window_share(run, "predict.launch")
