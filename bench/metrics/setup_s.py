"""Set-up: process start to the window's start (JAX start, engine,
warm-up of every program the traffic uses, compiles in a cold run)."""


def read(run):
    return run.setup_s
