"""Device time of the jitted decode-step program per decode step, from the
trace's XLA module events."""
from chipbench import trace

# module names of the engine's decode step as the trace shows them
MODULES = ("jit__step_impl",)


def read(run):
    if run.trace is None:
        return None
    ev = trace.matching(run.trace, "modules", lambda n: n.startswith(MODULES))
    if not ev:
        return None
    return 1e3 * trace.seconds_of(ev) / len(ev)
