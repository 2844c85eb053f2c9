"""Mean, over consecutive decode steps of the window, of the engine's own
host time while the device has no decode step: the next step's first host
copy minus the previous step's last, less the admissions, prefill chunks
and caller time between them (the program's engine spans)."""
from chipbench import spans


def read(run):
    got = spans.window_spans(run)
    return spans.mean(spans.host_gaps_ms(got)) if got else None
