"""Share of the window spent in the prediction service: outermost calls of
``prefetch_tables``, ``base_table`` and ``table``."""


def read(run):
    return 100.0 * run.spans.total("predict") / run.window_s
