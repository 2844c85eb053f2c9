"""Engine spans on the profiler's clock.

One process-wide :class:`Recorder` keeps the newest ``CAPACITY`` spans in
a ring and counts the ones it had to drop. A span is a name, a start and
an end on ``time.perf_counter_ns()``, the span that encloses it, the
request uid where it serves one request, and a few numeric attributes.
Each span also opens a ``jax.profiler.TraceAnnotation`` of the same name,
so a profile taken with ``jax.profiler.trace`` shows it on the profiler's
own clock beside the device ops. The recorder is always on; ``spans()``
reads it.

The serving engine (``ContinuousBatchingEngine.serve``) records:

- ``engine.admit``: from the pop of a request (page planning included) to
  its first token on the host; ``uid``, ``prompt`` tokens and the
  ``padded`` prefill length. A chunked admission's span ends once its
  pages are reserved: its prefill runs in ``engine.prefill_chunk`` spans.
  A pass whose request the page pool cannot cover yet has no ``uid``.
- ``engine.prefill_chunk``: the dispatch of one prefill chunk to its
  return (the final chunk's first token included); ``uid``, ``tokens``.
- ``engine.step``: the call to the decode step to the last of its
  outputs' host copies; ``step`` (the engine's decode-step count before
  it), ``lanes`` (lanes that emitted), and ``caller_ms``: the time the
  ``serve`` generator spent suspended at ``yield`` since the previous
  ``engine.step``. No span stays open across a ``yield``.
- ``engine.step.wait``, inside ``engine.step``: the first host copy of
  the step's outputs to the last.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

CAPACITY = 16384


class Span(NamedTuple):
    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: int                 # id of the enclosing span, -1 at the top
    uid: Optional[int]          # the request it serves, where there is one
    attrs: Dict[str, float]


class _OpenSpan:
    """A span while it is open; ``set`` adds what is known only inside."""

    __slots__ = ("_rec", "_name", "_id", "_parent", "_start", "_uid",
                 "_attrs", "_ann")

    def __init__(self, rec: "Recorder", name: str, uid, attrs):
        self._rec, self._name, self._uid, self._attrs = rec, name, uid, attrs

    def set(self, uid: Optional[int] = None, **attrs) -> None:
        if uid is not None:
            self._uid = uid
        self._attrs.update(attrs)

    def __enter__(self) -> "_OpenSpan":
        rec = self._rec
        stack = rec._stack()
        self._id = next(rec._ids)
        self._parent = stack[-1] if stack else -1
        stack.append(self._id)
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        if self._uid is not None:
            self._ann.set_metadata(uid=self._uid)
        if self._attrs:
            self._ann.set_metadata(**self._attrs)
        self._ann.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._append(Span(self._id, self._name, self._start, end,
                               self._parent, self._uid, self._attrs))


class Recorder:
    """A bounded ring of finished spans, oldest first."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._ids = itertools.count()
        self._lock = threading.Lock()
        self._local = threading.local()
        self.dropped = 0

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, span: Span) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(span)

    def span(self, name: str, uid: Optional[int] = None,
             **attrs) -> _OpenSpan:
        """Context manager that records ``name`` from entry to exit."""
        return _OpenSpan(self, name, uid, attrs)

    def spans(self) -> List[Span]:
        """The spans the ring holds, in the order they ended."""
        with self._lock:
            return list(self._ring)


RECORDER = Recorder()
span = RECORDER.span
spans = RECORDER.spans


def summary(recorded: List[Span]) -> Dict[str, float]:
    """Operator means in ms over ``recorded``: ``step``, ``wait``,
    ``admit`` and ``host_gap``. The host gap is the time from one decode
    step's last host copy to the next step's first, less the admissions,
    prefill chunks and caller time in between: the engine's own host time
    while the device has no decode step."""
    by = collections.defaultdict(list)
    for s in recorded:
        by[s.name].append(s)
    waits = {s.parent: s for s in by["engine.step.wait"]}
    steps = sorted((s for s in by["engine.step"] if s.id in waits),
                   key=lambda s: s.start_ns)
    busy = sorted((s.start_ns, s.end_ns) for s in
                  by["engine.admit"] + by["engine.prefill_chunk"])
    gaps = []
    for a, b in zip(steps, steps[1:]):
        lo, hi = waits[a.id].end_ns, waits[b.id].start_ns
        inside = sum(e - s for s, e in busy if s >= lo and e <= hi)
        gaps.append((hi - lo - inside) / 1e6 - b.attrs["caller_ms"])
    ms = lambda s: (s.end_ns - s.start_ns) / 1e6
    mean = lambda xs: sum(xs) / len(xs) if xs else float("nan")
    return {"step": mean([ms(s) for s in steps]),
            "wait": mean([ms(waits[s.id]) for s in steps]),
            "admit": mean([ms(s) for s in by["engine.admit"]
                           if s.uid is not None]),
            "host_gap": mean(gaps)}
