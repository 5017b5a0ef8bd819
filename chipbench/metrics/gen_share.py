"""Share of the window spent in the load generator: every ``next()`` of the
stream, new apps' profiling included."""


def read(run):
    return 100.0 * run.spans.total("gen") / run.window_s
