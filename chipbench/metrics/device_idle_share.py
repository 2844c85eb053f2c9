"""One minus the union of the device's op intervals over the traced
window."""
from chipbench import trace


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(run.trace)
                    / trace.window_seconds(run.trace))
