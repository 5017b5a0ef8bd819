"""Share of the window spent in the facility coordinator: ``advance``,
``offer``, ``escalate``, ``commit``, ``truncate``, ``next_release`` and
``potential_w``."""


def read(run):
    return 100.0 * run.spans.total("coord") / run.window_s
