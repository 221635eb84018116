"""setup_s: process start to the first timed operation (load, weights, warm-up, compiles)."""


def read(run):
    return run.setup_s
