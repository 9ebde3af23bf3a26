"""Seconds from process start to the window's start: JAX start-up, the
generator's start, the job's build, cache loads and compiles, and warm-up."""


def read(run):
    return run.setup_s
