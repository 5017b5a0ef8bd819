"""Percent of the jobs that reached admission control in the window and
were shed, not dispatched; nothing where the configuration has no
admission control."""


def read(run):
    if run.parts.get("admission") is None or run.placed + run.shed == 0:
        return None
    return 100.0 * run.shed / (run.placed + run.shed)
