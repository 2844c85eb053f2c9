"""Device time of the collective ops in a decode step, per device: on each
device the union of its collective op events (``trace.COLLECTIVES``, with
their ``-start``/``-done`` pairs) inside the window's decode-step program
events, over that device's count of them, averaged over the devices. A
trace with no collective (one chip) reads nothing."""
from chipbench import trace

# module names of the engine's decode step as the trace shows them
MODULES = ("jit__step_impl",)


def read(run):
    if run.trace is None:
        return None
    got = trace.collectives_per_step(run.trace, MODULES)
    return None if got is None else 1e3 * got[0]
