"""Seconds from the process's start to the window's opening: imports,
weights, engine, warm-up (compiles in a cold run, cache loads in a warm
one) and the ramp of arrivals before the window."""


def read(run):
    return run.setup_s
