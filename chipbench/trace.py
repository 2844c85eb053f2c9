"""From a profiler trace to numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
a compact record: per device plane, the events of its "XLA Ops" and "XLA
Modules" lines (a ``Line``: each distinct name once, and per event its
name's index, start and duration in ns, as arrays), and the benchmark's
own host spans (``chipbench.next``, one per ``next()`` on the engine's
event stream, with its call number). Everything else here works on that
record, so it can be checked on a small recorded trace; a line may also
be given as a list of [name, start_ns, duration_ns], as ``save`` writes
it. A four-chip window holds some ten million op events, so the
reductions work on arrays.

The traced window runs from the start of the first ``chipbench.next`` span
to the end of the last: the engine launches no device work outside those
calls while the trace runs.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

SPAN = "chipbench.next"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops whose time is the time of the ops inside them
CONTAINERS = ("%while", "%conditional", "%call")


def short(name: str) -> str:
    """An op event's name is its HLO instruction; keep the part before
    " = " (for example ``%aqua_paged_decode_attention.6``)."""
    return name.split(" = ", 1)[0]


@dataclasses.dataclass
class Line:
    """The events of one trace line: ``names`` holds each distinct short
    name once, ``ids`` each event's index into it."""

    names: List[str]
    ids: np.ndarray       # int64
    start: np.ndarray     # ns, float64
    dur: np.ndarray       # ns, float64

    @classmethod
    def of(cls, events) -> "Line":
        """A ``Line``, or one made from [name, start_ns, duration_ns]."""
        if isinstance(events, cls):
            return events
        index: Dict[str, int] = {}
        ids = [index.setdefault(short(e[0]), len(index)) for e in events]
        return cls(list(index), np.asarray(ids, np.int64),
                   np.asarray([e[1] for e in events], np.float64),
                   np.asarray([e[2] for e in events], np.float64))

    def rows(self) -> List[list]:
        return [[self.names[i], a, d] for i, a, d in
                zip(self.ids.tolist(), self.start.tolist(),
                    self.dur.tolist())]

    def where(self, pred: Callable[[str], bool]) -> np.ndarray:
        """Per event: whether its name satisfies ``pred``."""
        keep = np.fromiter((bool(pred(n)) for n in self.names), bool,
                           len(self.names))
        return keep[self.ids] if len(self.names) else np.zeros(0, bool)


def _read_line(line) -> Line:
    """A ``Line`` of a profiler line's events (the one pass over them)."""
    by_full: Dict[str, int] = {}     # full name -> index of its short name
    index: Dict[str, int] = {}       # short name -> index
    ids: List[int] = []
    start: List[float] = []
    dur: List[float] = []
    for e in line.events:
        n = e.name
        k = by_full.get(n)
        if k is None:
            k = by_full[n] = index.setdefault(short(n), len(index))
        ids.append(k)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    return Line(list(index), np.asarray(ids, np.int64),
                np.asarray(start, np.float64), np.asarray(dur, np.float64))


def extract(trace_dir: str) -> dict:
    """Compact record of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, dict] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            rec = {"ops": Line.of([]), "modules": Line.of([])}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is not None:
                    rec[key] = _read_line(line)
            devices[plane.name] = rec
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SPAN:
                        i = dict(e.stats).get("i", -1)
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), int(i)])
    host.sort(key=lambda s: s[1])
    return {"devices": devices, "host": host}


def _plain(rec: dict) -> dict:
    """``rec`` with every line as [name, start_ns, duration_ns] rows."""
    return {"devices": {k: {key: Line.of(v[key]).rows()
                            for key in ("ops", "modules")}
                        for k, v in rec["devices"].items()},
            "host": rec["host"]}


def save(rec: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(_plain(rec), f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(rec: dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the traced window."""
    spans = rec["host"]
    if not spans:
        raise ValueError("the trace holds no chipbench.next span")
    return spans[0][1], max(s[1] + s[2] for s in spans)


def _clip(events, lo: float, hi: float, keep: Optional[np.ndarray] = None
          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(ids, starts, ends) of the events clipped to [lo, hi], those left
    with no time dropped; ``keep`` selects events first."""
    ln = Line.of(events)
    ids, a, b = ln.ids, ln.start, ln.start + ln.dur
    if keep is not None:
        ids, a, b = ids[keep], a[keep], b[keep]
    a, b = np.maximum(a, lo), np.minimum(b, hi)
    inside = b > a
    return ids[inside], a[inside], b[inside]


def _union(a: np.ndarray, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Intervals [a, b) merged: sorted, disjoint (touching ones join)."""
    if a.size == 0:
        return a, b
    order = np.argsort(a, kind="stable")
    a, b = a[order], b[order]
    reach = np.maximum.accumulate(b)
    new = np.ones(a.size, bool)
    new[1:] = a[1:] > reach[:-1]
    first = np.flatnonzero(new)
    return a[first], np.maximum.reduceat(b, first)


def _intersect(s1, e1, s2, e2) -> Tuple[np.ndarray, np.ndarray]:
    """The intersection of two merged interval sets, as disjoint
    intervals."""
    pts = np.concatenate([s1, e1, s2, e2])
    if pts.size == 0:
        return pts, pts
    n1, n2 = s1.size, s2.size
    d1 = np.concatenate([np.ones(n1), -np.ones(n1), np.zeros(2 * n2)])
    d2 = np.concatenate([np.zeros(2 * n1), np.ones(n2), -np.ones(n2)])
    order = np.argsort(pts, kind="stable")
    pts = pts[order]
    both = (np.cumsum(d1[order]) > 0) & (np.cumsum(d2[order]) > 0)
    both, lo, hi = both[:-1], pts[:-1], pts[1:]
    keep = both & (hi > lo)
    return lo[keep], hi[keep]


def _length(iv: Tuple[np.ndarray, np.ndarray]) -> float:
    return float(np.sum(iv[1] - iv[0]))


def _busy(rec: dict, device: str) -> Tuple[np.ndarray, np.ndarray]:
    lo, hi = window(rec)
    _, a, b = _clip(rec["devices"][device]["ops"], lo, hi)
    return _union(a, b)


def merged_busy(rec: dict, device: str) -> List[Tuple[float, float]]:
    """Union of the device's op intervals inside the window, merged."""
    a, b = _busy(rec, device)
    return list(zip(a.tolist(), b.tolist()))


def busy_seconds(rec: dict) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    devs = list(rec["devices"])
    if not devs:
        return 0.0
    return sum(_length(_busy(rec, d)) for d in devs) / len(devs) / 1e9


def window_seconds(rec: dict) -> float:
    lo, hi = window(rec)
    return (hi - lo) / 1e9


class Events:
    """Window-clipped events, as arrays of starts and ends in ns."""

    def __init__(self, start: np.ndarray, end: np.ndarray):
        self.start, self.end = start, end

    def __len__(self) -> int:
        return int(self.start.size)


def matching(rec: dict, line: str, pred: Callable[[str], bool]) -> Events:
    """Window-clipped events of ``line`` ("ops" or "modules") on every
    device whose name satisfies ``pred``."""
    lo, hi = window(rec)
    starts, ends = [np.zeros(0)], [np.zeros(0)]
    for dev in rec["devices"].values():
        ln = Line.of(dev[line])
        _, a, b = _clip(ln, lo, hi, ln.where(pred))
        starts.append(a)
        ends.append(b)
    return Events(np.concatenate(starts), np.concatenate(ends))


def seconds_of(events: Events) -> float:
    return float(np.sum(events.end - events.start)) / 1e9


# collectives as the TPU trace names their ops: the HLO instruction and
# its number (``%all-reduce.5``), an asynchronous one as a ``-start`` and
# ``-done`` pair (``%all-gather-start.2``)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_COLLECTIVE_OPS = frozenset(c + part for c in COLLECTIVES
                            for part in ("", "-start", "-done"))


def is_collective(name: str) -> bool:
    """Whether an op event (its short name) is a collective."""
    return name.lstrip("%").split(".", 1)[0] in _COLLECTIVE_OPS


def _container(name: str) -> bool:
    return name.startswith(CONTAINERS)


def collectives_per_step(rec: dict, modules: Tuple[str, ...]
                         ) -> Optional[Tuple[float, float]]:
    """(collective, exposed) seconds per module event, averaged over the
    devices: on each device the union of its collective ops that lie in
    the window's events of ``modules`` (a decode step's program), and the
    part of it in which no other op (containers left out) runs there; each
    over that device's count of those events. None where no device ran
    such a module or the window holds no collective."""
    lo, hi = window(rec)
    per_dev = []
    found = False
    for dev in rec["devices"].values():
        mods = Line.of(dev["modules"])
        _, ma, mb = _clip(mods, lo, hi,
                          mods.where(lambda n: n.startswith(modules)))
        if not ma.size:
            continue
        steps = _union(ma, mb)
        ops = Line.of(dev["ops"])
        coll = ops.where(is_collective)
        _, ca, cb = _clip(ops, lo, hi, coll)
        found = found or bool(ca.size)
        inside = _intersect(*_union(ca, cb), *steps)
        other = _union(*_clip(ops, lo, hi,
                              ~coll & ~ops.where(_container))[1:])
        both = _length(_intersect(*inside, *other))
        per_dev.append((_length(inside) / ma.size,
                        (_length(inside) - both) / ma.size))
    if not per_dev or not found:
        return None
    return (sum(c for c, _ in per_dev) / len(per_dev) / 1e9,
            sum(e for _, e in per_dev) / len(per_dev) / 1e9)


def top_ops(rec: dict, n: int = 10) -> List[list]:
    """The ``n`` op names with the most device time in the window, summed
    over calls, averaged over devices, loops and other containers left
    out: [[name, seconds], ...]."""
    lo, hi = window(rec)
    tot: Dict[str, float] = {}
    devs = list(rec["devices"].values())
    for dev in devs:
        ops = Line.of(dev["ops"])
        ids, a, b = _clip(ops, lo, hi, ~ops.where(_container))
        sums = np.bincount(ids, weights=b - a, minlength=len(ops.names))
        for k in np.flatnonzero(sums):
            name = ops.names[k]
            tot[name] = tot.get(name, 0.0) + float(sums[k]) / 1e9
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(devs), 1)] for k, v in ranked]


def idle_gaps(rec: dict, labels: Optional[Dict[int, str]] = None,
              n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of the first device inside the window,
    each named by what the host was doing at its midpoint: the label of
    the ``chipbench.next`` call covering it (``labels`` maps call numbers
    to names), else "benchmark loop". [[name, seconds], ...]."""
    devs = sorted(rec["devices"])
    if not devs:
        return []
    lo, hi = window(rec)
    a, b = _busy(rec, devs[0])
    ga = np.concatenate([[lo], b])
    gb = np.concatenate([a, [hi]])
    gap = gb - ga
    order = np.argsort(-gap, kind="stable")
    order = order[gap[order] > 0][:n]
    spans = rec["host"]
    starts = [s[1] for s in spans]
    out = []
    for k in order.tolist():
        mid = (ga[k] + gb[k]) / 2
        name = "benchmark loop"
        j = bisect.bisect_right(starts, mid) - 1
        for _, s, d, i in spans[max(j - 1, 0):j + 1][::-1]:
            if s <= mid <= s + d:
                name = (labels or {}).get(i, SPAN)
                break
        out.append([name, float(gap[k]) / 1e9])
    return out


def trim(rec: dict, max_spans: int) -> dict:
    """The first ``max_spans`` host spans and the device events inside
    them: a small trace to keep for tests."""
    spans = rec["host"][:max_spans]
    hi = max(s[1] + s[2] for s in spans)
    lo = spans[0][1]
    keep = lambda evs: [e for e in Line.of(evs).rows()
                        if e[1] < hi and e[1] + e[2] > lo]
    return {"devices": {k: {"ops": keep(v["ops"]),
                            "modules": keep(v["modules"])}
                        for k, v in rec["devices"].items()},
            "host": spans}
