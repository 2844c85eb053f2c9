"""The part of ``collective_ms`` in which no other op runs on the same
device (loops and other containers left out): the time a decode step
waits on the exchange between chips, per device. A trace with no
collective (one chip) reads nothing."""
from chipbench import trace

# module names of the engine's decode step as the trace shows them
MODULES = ("jit__step_impl",)


def read(run):
    if run.trace is None:
        return None
    got = trace.collectives_per_step(run.trace, MODULES)
    return None if got is None else 1e3 * got[1]
