"""Mean length of the program's ``engine.admit`` spans that end in the
window: one admission inside the engine, from the pop of the request to
its first token on the host."""
from chipbench import spans


def read(run):
    got = spans.window_spans(run)
    if not got:
        return None
    return spans.mean([(s.end_ns - s.start_ns) / 1e6 for s in got
                       if s.name == "engine.admit" and s.uid is not None])
