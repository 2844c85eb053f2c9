"""The engine readers (``host_gap_ms``, ``caller_ms``, ``admit_ms``) on a
hand-built span list, and their silence without a device trace, without
spans in the window, or on a program that records none."""
import sys

import pytest

from chipbench import spec
from chipbench.tests.conftest import ROOT
from chipbench.tests.test_metrics import run_of, window

MS = 1_000_000
T0 = 100 * 1000 * MS           # the window opens at 100 s (perf_counter)
TRACE = {"devices": {"/device:TPU:0": {"ops": [], "modules": []}},
         "host": []}
NAMES = ("host_gap_ms", "caller_ms", "admit_ms")


def read(name, run):
    return spec.load_reader(ROOT, name)(run)


def span(i, name, a_ms, b_ms, parent=-1, uid=None, **attrs):
    from repro.serving.telemetry import Span
    return Span(i, name, T0 + int(a_ms * MS), T0 + int(b_ms * MS), parent,
                uid, attrs)


def steps(i, at_ms, caller_ms, wait_ms=250.0):
    """An ``engine.step`` of 1 ms dispatch and its ``wait_ms`` wait."""
    return [span(i + 1, "engine.step.wait", at_ms + 1, at_ms + 1 + wait_ms,
                 parent=i),
            span(i, "engine.step", at_ms, at_ms + 1 + wait_ms,
                 step=i, lanes=4, caller_ms=caller_ms)]


# three decode steps in the window, an admission of 40 ms between the
# last two, and spans before the window that the readers leave out
SPANS = (steps(0, -300, 0.5)
         + [span(9, "engine.admit", -40, -5, uid=3, prompt=100, padded=128)]
         + steps(10, 5, 0.9)
         + steps(20, 261, 0.3)
         + [span(29, "engine.admit", 513, 553, uid=4, prompt=90, padded=128),
            span(30, "engine.admit", 555, 556)]        # pool full: no uid
         + steps(40, 560, 0.7))


@pytest.fixture
def recorded(monkeypatch):
    from repro.serving import telemetry
    monkeypatch.setattr(telemetry, "spans", lambda: list(SPANS))


def run_with(trace=TRACE):
    win = window({}, t_open=T0 / 1e9, t_close=T0 / 1e9 + 1.0)
    return run_of(win, trace=trace)


def test_readers_on_a_span_list(recorded):
    run = run_with()
    # gaps: 262 - 256 - 0.3 = 5.7 ms; 561 - 512 - 40 (admission) - 1
    # (the pool-full pass) - 0.7 = 7.3 ms
    assert read("host_gap_ms", run) == pytest.approx((5.7 + 7.3) / 2)
    assert read("caller_ms", run) == pytest.approx((0.3 + 0.7) / 2)
    assert read("admit_ms", run) == pytest.approx(40.0)


def test_readers_are_silent_without_a_device_trace(recorded):
    for trace in (None, {"devices": {}, "host": []}):
        for name in NAMES:
            assert read(name, run_with(trace)) is None


def test_readers_are_silent_without_spans_in_the_window(monkeypatch):
    from repro.serving import telemetry
    monkeypatch.setattr(telemetry, "spans", lambda: SPANS[:3])
    for name in NAMES:
        assert read(name, run_with()) is None


def test_readers_are_silent_on_a_program_without_the_recorder(monkeypatch):
    import repro.serving
    monkeypatch.delattr(repro.serving, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.serving.telemetry", None)
    for name in NAMES:
        assert read(name, run_with()) is None
