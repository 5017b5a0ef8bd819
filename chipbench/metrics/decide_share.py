"""Share of the window spent in the policy's decisions: outermost calls of
``select_device_clock`` (the joint class and clock choice) and
``select_capped`` (the clock under a power grant)."""


def read(run):
    return 100.0 * run.spans.total("decide") / run.window_s
