"""Programs lowered inside the window, each of which is then compiled or
loaded from the persistent cache; set-up warms every kernel shape, so this
should be 0."""


def read(run):
    return run.compiles
