"""Seconds from process start to the window's opening: imports, weights,
calibration, engine, compilation or cache loads, and the first wave."""


def read(run):
    return run.setup_s
