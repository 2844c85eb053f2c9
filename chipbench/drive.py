"""Drives the program's continuous-batching engine through one window.

Set-up admits the first wave of ``lanes`` requests (one prefill per bucket
the mix uses, so every admission shape is compiled) and runs decode steps
until the window's opening condition holds: a decode step has completed,
and at least ``open_after_completions`` requests have completed. The window
then runs for ``seconds`` and closes on the first whole unit of work (a
decode step's tokens, or one admission's first token) that ends past it.

Every ``StreamEvent`` is stamped on the host clock as it is received. The
engine copies each step's tokens to the host before it yields them, so a
received token is a finished device step; the engine launches no device
work while it yields the tokens of one step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Unit:
    """One unit of work the window received: a decode step or an
    admission. ``tokens`` lists (uid, index) in the order received."""

    kind: str                     # "step" | "admit"
    t_end: float                  # host time of its last token
    tokens: list


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float                # host time of the last counted token
    units: List[Unit]             # the window's work, in order
    # every token of the drive: uid -> [(host time, index)]
    times: Dict[int, list]
    served: Dict[int, list]       # uid -> tokens in index order
    finished: Dict[int, float]    # uid -> host time of its last token
    completion_order: List[int]   # uids in the order they completed
    admission_order: List[int]    # uids in the order they were admitted
    steps_open: int               # engine decode steps at the opening
    occupancy_open: int
    steps_close: int
    occupancy_close: int
    labels: Dict[int, str]        # next() call number -> what it returned


def mesh_shape(conf: dict) -> Optional[tuple]:
    """The deployment's mesh over ("data", "model") that the
    configuration's ``serve.mesh_shape`` states; None serves on one
    device."""
    shape = conf["serve"].get("mesh_shape")
    return None if shape is None else tuple(int(n) for n in shape)


def chips(conf: dict) -> int:
    """Devices the configuration serves on: its mesh's size, else 1."""
    return math.prod(mesh_shape(conf) or (1,))


def serving_config(lanes: int, max_seq: int, conf: dict, mix: dict):
    """The program's ``ServingConfig`` for a cell: its lanes, cache, paging
    and, where the configuration states one, its mesh."""
    from repro.configs.base import CacheSpec, ServingConfig
    serve = conf["serve"]
    return ServingConfig(
        max_lanes=lanes, max_seq=max_seq, temperature=mix["temperature"],
        prompt_bucket=mix["prompt_bucket"],
        cache=CacheSpec(page_size=serve["page_size"],
                        prefix_sharing=serve["prefix_sharing"]),
        mesh_shape=mesh_shape(conf))


def build_engine(cfg, params, proj, lanes: int, max_seq: int, conf: dict,
                 mix: dict):
    from repro.core.calibration import AquaProjections
    from repro.serving.engine import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        cfg, params, AquaProjections(p=proj),
        serving=serving_config(lanes, max_seq, conf, mix))


def requests(planned, temperature: float):
    from repro.serving.scheduler import Request
    return [Request(uid=p.uid, tokens=p.tokens, max_new_tokens=p.max_new,
                    temperature=temperature, arrival=0.0) for p in planned]


def drive(eng, reqs, *, seconds: float, open_after_completions: int,
          clock: Callable[[], float], on_open: Optional[Callable] = None,
          on_close: Optional[Callable] = None,
          annotate: Optional[Callable] = None) -> Window:
    """Run set-up and one window; return the window's records. ``on_open``
    and ``on_close`` run at the window's edges (the profiler);
    ``annotate(i)`` returns a context that spans the i-th ``next()``."""
    gen = eng.serve(reqs)
    stats = eng.stats
    times: Dict[int, list] = {}
    served: Dict[int, list] = {}
    finished: Dict[int, float] = {}
    completion_order: List[int] = []
    admission_order: List[int] = []
    units: List[Unit] = []
    t_open = None
    deadline = None
    step_id = -1
    step_left = 0          # tokens of the current decode step still to come
    occ_seen = 0
    open_marks = close_marks = (0, 0)
    labels: Dict[int, str] = {}
    i = 0
    while True:
        if annotate is not None:
            with annotate(i):
                ev = next(gen, None)
        else:
            ev = next(gen, None)
        labels[i] = "engine: admission" if ev is not None and ev.index == 0 \
            else "engine: decode step"
        i += 1
        if ev is None:
            raise RuntimeError("the backlog drained before the window "
                               "closed: raise traffic.BACKLOG_WAVES")
        t = clock()
        stats = eng.stats
        times.setdefault(ev.uid, []).append((t, ev.index))
        served.setdefault(ev.uid, []).append(ev.token)
        if ev.finished:
            finished[ev.uid] = t
            completion_order.append(ev.uid)
        if ev.index == 0:
            admission_order.append(ev.uid)
            unit_done = True
            if t_open is not None:
                units.append(Unit("admit", t, [(ev.uid, 0)]))
        else:
            if stats.decode_steps != step_id:
                step_id = stats.decode_steps
                step_left = stats.occupancy_sum - occ_seen
                occ_seen = stats.occupancy_sum
                if t_open is not None:
                    units.append(Unit("step", t, []))
            step_left -= 1
            unit_done = step_left == 0
            if t_open is not None:
                units[-1].tokens.append((ev.uid, ev.index))
                units[-1].t_end = t
        if not unit_done:
            continue
        if t_open is None:
            if (ev.index > 0 and len(completion_order)
                    >= open_after_completions):
                if on_open is not None:
                    on_open()
                t_open = clock()
                deadline = t_open + seconds
                open_marks = (stats.decode_steps, stats.occupancy_sum)
            continue
        if t >= deadline:
            close_marks = (stats.decode_steps, stats.occupancy_sum)
            break
    t_close = units[-1].t_end
    if on_close is not None:
        on_close()
    gen.close()
    return Window(t_open=t_open, t_close=t_close, units=units, times=times,
                  served=served, finished=finished,
                  completion_order=completion_order,
                  admission_order=admission_order,
                  steps_open=open_marks[0], occupancy_open=open_marks[1],
                  steps_close=close_marks[0], occupancy_close=close_marks[1],
                  labels=labels)


def window_tokens(w: Window) -> int:
    return sum(len(u.tokens) for u in w.units)


def itl_gaps(w: Window) -> List[float]:
    """Gaps between consecutive tokens of one request, both inside the
    window, over all requests."""
    out = []
    for ts in w.times.values():
        inside = [t for t, _ in ts if w.t_open < t <= w.t_close]
        out.extend(np.diff(inside).tolist())
    return out


def ttfts(w: Window, lanes: int) -> List[float]:
    """First-token time minus release time, for every request whose first
    token falls in the window. Request ``uid`` >= ``lanes`` is released by
    the (uid - lanes + 1)-th completion of the drive (closed loop)."""
    out = []
    for uid, ts in w.times.items():
        t_first, idx = ts[0]
        if idx != 0 or not (w.t_open < t_first <= w.t_close) or uid < lanes:
            continue
        k = uid - lanes
        released_by = w.completion_order[k]
        out.append(t_first - w.finished[released_by])
    return out
