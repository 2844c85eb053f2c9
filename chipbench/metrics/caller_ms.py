"""Mean time per decode step of the window that the engine's event stream
spent suspended in the benchmark's loop (the ``caller_ms`` of each step
after the window's first, from the program's engine spans): the caller's
share of the gap between steps."""
from chipbench import spans


def read(run):
    got = spans.window_spans(run)
    if not got:
        return None
    return spans.mean([b.attrs["caller_ms"]
                       for _, (b, _) in spans.step_pairs(got)])
