"""setup_s: process start to window start, compilation or cache loading,
data and warm-up included."""


def read(run):
    return run.setup_s
