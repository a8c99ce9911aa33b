"""Seconds from the process's start to the window's opening: imports,
weights, compiles or cache loads, warm-up, and the traffic served before."""


def read(run):
    return run.setup_s
