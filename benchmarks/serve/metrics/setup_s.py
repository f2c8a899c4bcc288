"""Set-up: process start to the window's start (weights, engine, warm-up
and, in a run that compiles, compilation)."""

UNIT = "s"


def read(run):
    return run.setup_s
