"""The program's engine spans inside the window, for the engine readers.

The program records spans in ``repro.serving.telemetry`` on
``time.perf_counter_ns()``, the clock of the window's edges
(``chipbench/run.py`` drives with ``clock=time.perf_counter``). The
readers that use them split the device's idle time between the engine's
own host work and the caller, so they read only a traced run with a
device plane, and return None where the program records no spans or the
window holds none.

The reduction from spans to numbers is kept here, with the benchmark, so
a change to the program cannot move it.
"""
from __future__ import annotations

from typing import List, Optional


def window_spans(run) -> Optional[list]:
    """The program's spans that end inside the window, or None."""
    if run.trace is None or not run.trace["devices"]:
        return None
    try:
        from repro.serving import telemetry
    except ImportError:       # a program without the recorder
        return None
    lo, hi = run.window.t_open * 1e9, run.window.t_close * 1e9
    return [s for s in telemetry.spans() if lo <= s.end_ns <= hi]


def step_pairs(spans: list) -> List[tuple]:
    """Consecutive decode steps as ((step, wait), (step, wait)) pairs:
    each ``engine.step`` with its child ``engine.step.wait``, by start."""
    waits = {s.parent: s for s in spans if s.name == "engine.step.wait"}
    steps = sorted(((s, waits[s.id]) for s in spans
                    if s.name == "engine.step" and s.id in waits),
                   key=lambda sw: sw[0].start_ns)
    return list(zip(steps, steps[1:]))


def host_gaps_ms(spans: list) -> List[float]:
    """For each pair of consecutive decode steps: the next step's first
    host copy minus the previous step's last, less the admissions and
    prefill chunks between them and the next step's ``caller_ms``."""
    busy = [(s.start_ns, s.end_ns) for s in spans
            if s.name in ("engine.admit", "engine.prefill_chunk")]
    out = []
    for (_, wa), (sb, wb) in step_pairs(spans):
        lo, hi = wa.end_ns, wb.start_ns
        inside = sum(e - s for s, e in busy if lo <= s and e <= hi)
        out.append((hi - lo - inside) / 1e6 - sb.attrs["caller_ms"])
    return out


def mean(xs: list) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None
