"""Share of the traced window (the suite's table prefetch and the measured
window) in which no operation ran on the chip."""


def read(run):
    if run.traced_window_s <= 0.0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.traced_window_s)
