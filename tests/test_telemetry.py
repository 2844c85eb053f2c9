"""Engine spans (``repro.serving.telemetry``) on a tiny model through
``serve``: their nesting and counts, caller time kept out of every span,
the bounded ring, and their line-up with the profiler's own clock."""
import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import reduced
from repro.configs.base import CacheSpec, ServingConfig
from repro.models import build_model
from repro.serving import ContinuousBatchingEngine, Request, telemetry

SCFG = ServingConfig(max_lanes=2, max_seq=64, max_new_tokens=5,
                     prompt_bucket=8,
                     cache=CacheSpec(page_size=8, num_pages=16))


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(reduced("qwen3-0.6b"), remat=False,
                              dtype="float32", aqua=None)
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


def _engine(model, budget=None):
    cfg, params = model
    scfg = dataclasses.replace(SCFG, prefill_budget_tokens=budget)
    return ContinuousBatchingEngine(cfg, params, None, serving=scfg,
                                    backend="dense-jnp")


def _requests(n=4, seed=0, lo=4, hi=20):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, 512, size=int(
        rng.integers(lo, hi)), dtype=np.int32), max_new_tokens=4)
        for i in range(n)]


def _drive(eng, reqs, on_event=None):
    """Serve ``reqs``; return the spans recorded during the drive."""
    mark = time.perf_counter_ns()
    for ev in eng.serve(reqs):
        if on_event is not None:
            on_event(ev)
    return [s for s in telemetry.spans() if s.start_ns >= mark]


def _named(spans, name):
    return [s for s in spans if s.name == name]


@pytest.fixture(scope="module")
def warm(model):
    eng = _engine(model)
    _drive(eng, _requests())            # compile every shape first
    return eng


def test_one_span_per_step_and_admission(warm):
    reqs = _requests()
    got = _drive(warm, reqs)
    steps, waits = _named(got, "engine.step"), _named(got, "engine.step.wait")
    admits = _named(got, "engine.admit")
    assert len(steps) == warm.stats.decode_steps
    assert [s.attrs["step"] for s in steps] == list(range(len(steps)))
    assert sorted(w.parent for w in waits) == sorted(s.id for s in steps)
    for s in steps:
        w = next(w for w in waits if w.parent == s.id)
        assert s.start_ns <= w.start_ns <= w.end_ns <= s.end_ns
        assert s.parent == -1 and 1 <= s.attrs["lanes"] <= SCFG.max_lanes
    assert sum(s.attrs["lanes"] for s in steps) == warm.stats.occupancy_sum
    assert sorted(a.uid for a in admits) == [r.uid for r in reqs]
    for a, r in zip(sorted(admits, key=lambda a: a.uid), reqs):
        assert a.attrs["prompt"] == len(r.tokens)
        assert a.attrs["padded"] == -(-len(r.tokens) // 8) * 8
    # no span of the engine overlaps another at the top level
    top = sorted((s for s in got if s.parent == -1),
                 key=lambda s: s.start_ns)
    assert all(a.end_ns <= b.start_ns for a, b in zip(top, top[1:]))


def test_chunked_admissions_record_their_chunks(model):
    eng = _engine(model, budget=8)
    assert eng.dispatch_plan().chunked_prefill
    reqs = _requests(lo=12, hi=30)
    got = _drive(eng, reqs)
    chunks = _named(got, "engine.prefill_chunk")
    assert len(chunks) == eng.stats.prefill_chunks
    for r in reqs:
        mine = [c for c in chunks if c.uid == r.uid]
        assert sum(c.attrs["tokens"] for c in mine) == len(r.tokens)
    assert sorted(a.uid for a in _named(got, "engine.admit")) == [
        r.uid for r in reqs]


def test_caller_time_stays_out_of_every_span(warm):
    sleeps = []

    def slow(ev):
        t = time.perf_counter_ns()
        time.sleep(0.02)
        sleeps.append((t, time.perf_counter_ns()))

    got = _drive(warm, _requests(seed=1), on_event=slow)
    for s in got:
        assert not any(a < s.end_ns and s.start_ns < b for a, b in sleeps), \
            f"{s.name} spans a sleep of the caller"
    steps = sorted(_named(got, "engine.step"), key=lambda s: s.start_ns)
    for prev, step in zip([None] + steps, steps):
        lo = prev.end_ns if prev is not None else 0
        slept = sum(b - a for a, b in sleeps
                    if lo <= a and b <= step.start_ns) / 1e6
        assert slept >= 20.0 or prev is None
        assert slept <= step.attrs["caller_ms"] <= slept + 5.0


def test_ring_is_bounded_and_counts_drops():
    rec = telemetry.Recorder(capacity=4)
    for i in range(10):
        with rec.span("outer", uid=i) as sp:
            with rec.span("inner"):
                pass
            sp.set(k=i)
    got = rec.spans()
    assert len(got) == 4 and rec.dropped == 16
    assert [(s.name, s.uid) for s in got] == [
        ("inner", None), ("outer", 8), ("inner", None), ("outer", 9)]
    assert got[0].parent == got[1].id and got[1].attrs == {"k": 8}


def test_summary_reads_the_host_gap():
    def s(i, name, a, b, parent=-1, uid=None, **attrs):
        return telemetry.Span(i, name, a, b, parent, uid, attrs)

    ms = 1_000_000
    recorded = [
        s(0, "engine.step", 0, 10 * ms, step=0, lanes=2, caller_ms=0.0),
        s(1, "engine.step.wait", 2 * ms, 10 * ms, parent=0),
        s(2, "engine.admit", 11 * ms, 41 * ms, uid=7),
        s(3, "engine.step", 45 * ms, 55 * ms, step=1, lanes=2,
          caller_ms=1.0),
        s(4, "engine.step.wait", 47 * ms, 55 * ms, parent=3)]
    got = telemetry.summary(recorded)
    # 47 - 10 ms, less the 30 ms admission and 1 ms in the caller
    assert got == {"step": 10.0, "wait": 8.0, "admit": 30.0,
                   "host_gap": 6.0}


def test_step_waits_line_up_with_the_profiler(warm, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        got = _drive(warm, _requests(seed=2))
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    traced = sorted(((e.start_ns, e.start_ns + e.duration_ns)
                     for p in pd.planes if p.name.startswith("/host:")
                     for line in p.lines for e in line.events
                     if e.name == "engine.step.wait"))
    stamped = sorted((s.start_ns, s.end_ns)
                     for s in _named(got, "engine.step.wait"))
    assert len(traced) == len(stamped) > 3
    offsets = [t - s for pair in zip(traced, stamped)
               for t, s in zip(*pair)]
    assert max(offsets) - min(offsets) < 0.2e6, "spread over 0.2 ms"
