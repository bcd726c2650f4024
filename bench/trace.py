"""Reduction of a profiler trace to the numbers the metrics read.

A traced run records the JAX profiler over the measured window. This module
turns the ``.xplane.pb`` it writes into a flat list of events, and the list
into: the window (the harness's ``bench.window`` span), each device's busy
time (the union of its op intervals inside the window), the device time of
modules whose name matches a pattern, the device operations that took most
time, and the longest idle gaps, each named by the innermost host event
that covers it. The same code reads the small recorded trace in
``tests/``.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class NoMatchingEvents(LookupError):
    """A trace holds no device event of the kind a metric needs."""


def load_xplane(trace_dir: str) -> List[Event]:
    """Events of the device planes and the host plane of the newest trace
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not (plane.name.startswith(DEVICE_PREFIX)
                or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            if plane.name != HOST_PLANE and line.name not in (OPS_LINE,
                                                              MODULES_LINE):
                continue
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def save_events(events: Sequence[Event], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


class TraceSummary:
    """What the metric readers take from one traced window."""

    def __init__(self, events: Sequence[Event],
                 devices: Optional[Sequence[int]] = None):
        spans = [e for e in events if e.plane == HOST_PLANE
                 and e.name == WINDOW_SPAN]
        if not spans:
            raise NoMatchingEvents(f"no {WINDOW_SPAN!r} span in the trace")
        win = max(spans, key=lambda e: e.dur_ns)
        self.t0, self.t1 = win.start_ns, win.end_ns
        self.window_s = (self.t1 - self.t0) / 1e9
        planes = sorted({e.plane for e in events
                         if e.plane.startswith(DEVICE_PREFIX)})
        if devices is not None:
            planes = [p for p in planes
                      if int(p[len(DEVICE_PREFIX):]) in set(devices)]
        self.planes = planes
        self.host = [e for e in events if e.plane == HOST_PLANE]
        self.ops = {p: self._clip(e for e in events if e.plane == p
                                  and e.line == OPS_LINE) for p in planes}
        self.modules = {p: self._clip(e for e in events if e.plane == p
                                      and e.line == MODULES_LINE)
                        for p in planes}
        if not any(self.ops.values()):
            raise NoMatchingEvents("no device op ran inside the window")
        self.busy = {p: _union([(s, e) for _, s, e in self.ops[p]])
                     for p in planes}

    def _clip(self, evs) -> List[Tuple[str, float, float]]:
        out = []
        for e in evs:
            s, t = max(e.start_ns, self.t0), min(e.end_ns, self.t1)
            if t > s:
                out.append((e.name, s, t))
        return out

    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the devices traced."""
        per = [sum(e - s for s, e in self.busy[p]) / 1e9 for p in self.planes]
        return sum(per) / len(per)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def module_seconds(self, pattern: str) -> float:
        """Summed device time of modules whose name matches ``pattern``,
        over all devices traced; raises if none matches."""
        rx = re.compile(pattern)
        total, hits = 0.0, 0
        for p in self.planes:
            for name, s, e in self.modules[p]:
                if rx.search(name):
                    total += e - s
                    hits += 1
        if not hits:
            raise NoMatchingEvents(f"no device module matches {pattern!r}")
        return total / 1e9

    def top_modules(self, k: int = 10) -> List[List]:
        acc: Dict[str, float] = defaultdict(float)
        for p in self.planes:
            for name, s, e in self.modules[p]:
                acc[name] += (e - s) / 1e9
        return [[n, s] for n, s in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of the first device, each named by the
        innermost host event covering its midpoint."""
        p = self.planes[0]
        gaps, last = [], self.t0
        for s, e in self.busy[p] + [(self.t1, self.t1)]:
            if s > last:
                gaps.append((last, s))
            last = max(last, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            cover = [h for h in self.host
                     if h.start_ns <= mid <= h.end_ns
                     and h.name != WINDOW_SPAN]
            name = (min(cover, key=lambda h: h.dur_ns).name if cover
                    else "no host event")
            out.append([name, (e - s) / 1e9])
        return out
