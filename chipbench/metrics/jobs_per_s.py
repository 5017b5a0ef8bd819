"""Jobs placed (dispatched or shed) per second of the whole window, the
drain after the last arrival included."""


def read(run):
    return run.placed / run.window_s
