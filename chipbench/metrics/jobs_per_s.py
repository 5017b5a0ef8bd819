"""Jobs dispatched per second of the whole window, the drain after the last
arrival included; a job that admission control shed is not counted."""


def read(run):
    return run.placed / run.window_s
