"""setup_s: process start to the first timed op (jax start, compile cache
loads or compiles, the load of the data set, warm-up)."""


def read(run):
    return run.setup_s
