"""Process start to the first timed job: imports, profiling, the predictor
fit, kernel shapes and the suite's tables."""


def read(run):
    return run.setup_s
