"""From a profiler trace to numbers.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
a compact record: per device plane, the events of its "XLA Ops" and "XLA
Modules" lines as [name, start_ns, duration_ns], and the benchmark's own
host spans (``chipbench.next``, one per ``next()`` on the engine's event
stream, with its call number). Everything else here works on that record,
so it can be checked on a small recorded trace.

The traced window runs from the start of the first ``chipbench.next`` span
to the end of the last: the engine launches no device work outside those
calls while the trace runs.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Callable, Dict, Iterable, List, Optional, Tuple

SPAN = "chipbench.next"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops whose time is the time of the ops inside them
CONTAINERS = ("%while", "%conditional", "%call")


def short(name: str) -> str:
    """An op event's name is its HLO instruction; keep the part before
    " = " (for example ``%aqua_paged_decode_attention.6``)."""
    return name.split(" = ", 1)[0]


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
            rec = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                rec[key] = [[short(e.name), float(e.start_ns),
                             float(e.duration_ns)] for e in line.events]
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


def save(rec: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rec, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(rec: dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the traced window."""
    spans = rec["host"]
    if not spans:
        raise ValueError("the trace holds no chipbench.next span")
    return spans[0][1], max(s[1] + s[2] for s in spans)


def _clip(events: Iterable, lo: float, hi: float) -> List[tuple]:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def merged_busy(rec: dict, device: str) -> List[Tuple[float, float]]:
    """Union of the device's op intervals inside the window, merged."""
    lo, hi = window(rec)
    iv = sorted((a, b) for _, a, b in _clip(rec["devices"][device]["ops"],
                                             lo, hi))
    out: List[Tuple[float, float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_seconds(rec: dict) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    devs = list(rec["devices"])
    if not devs:
        return 0.0
    return sum(sum(b - a for a, b in merged_busy(rec, d))
               for d in devs) / len(devs) / 1e9


def window_seconds(rec: dict) -> float:
    lo, hi = window(rec)
    return (hi - lo) / 1e9


def matching(rec: dict, line: str, pred: Callable[[str], bool]
             ) -> List[tuple]:
    """Window-clipped events of ``line`` ("ops" or "modules") on every
    device whose name satisfies ``pred``."""
    lo, hi = window(rec)
    out = []
    for dev in rec["devices"].values():
        out += [e for e in _clip(dev[line], lo, hi) if pred(e[0])]
    return out


def seconds_of(events: List[tuple]) -> float:
    return sum(b - a for _, a, b in events) / 1e9


def top_ops(rec: dict, n: int = 10) -> List[list]:
    """The ``n`` op names with the most device time in the window, summed
    over calls, averaged over devices, loops and other containers left
    out: [[name, seconds], ...]."""
    lo, hi = window(rec)
    tot: Dict[str, float] = {}
    devs = list(rec["devices"].values())
    for dev in devs:
        for name, a, b in _clip(dev["ops"], lo, hi):
            if name.startswith(CONTAINERS):
                continue
            tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
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
    busy = merged_busy(rec, devs[0])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]
    spans = rec["host"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        name = "benchmark loop"
        for _, s, d, i in spans:
            if s <= mid <= s + d:
                name = (labels or {}).get(i, SPAN)
                break
        out.append([name, (b - a) / 1e9])
    return out


def trim(rec: dict, max_spans: int) -> dict:
    """The first ``max_spans`` host spans and the device events inside
    them: a small trace to keep for tests."""
    spans = rec["host"][:max_spans]
    hi = max(s[1] + s[2] for s in spans)
    lo = spans[0][1]
    keep = lambda evs: [[short(e[0])] + e[1:] for e in evs
                        if e[1] < hi and e[1] + e[2] > lo]
    return {"devices": {k: {"ops": keep(v["ops"]),
                            "modules": keep(v["modules"])}
                        for k, v in rec["devices"].items()},
            "host": spans}
