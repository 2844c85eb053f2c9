"""Emitting lanes per decode step over the lanes, in the window (the
engine's own step and occupancy counters, read at the window's edges)."""


def read(run):
    w = run.window
    steps = w.steps_close - w.steps_open
    if steps <= 0:
        return None
    return 100.0 * (w.occupancy_close - w.occupancy_open) / (steps * run.lanes)
