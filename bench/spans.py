"""The program's own spans and counters, as the per-layer metrics read them.

The program opens named spans on the host plane of the same trace as the
device ops (``repro.core.spans``): the scheduler's hold and the phases of
each engine dispatch. ``idle_split`` takes each device's idle intervals
inside the window (the complement of ``TraceSummary.busy``) and names each
idle stretch after the innermost program span over it, the shortest that
covers it; what no program span covers is ``OUTSIDE``. The rows of a split,
averaged over the devices traced as ``TraceSummary.busy_s`` does, sum to
the window's idle time.

The names are copied here, not imported, so that the yardstick cannot move
with the program.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SCHED_HOLD = "sched.hold"
DISPATCH = "gcn.dispatch"
WAIT = "gcn.dispatch.wait"
PLAN_BUILD = "gcn.plan.build"
HOST_WORK = (SCHED_HOLD, "gcn.dispatch.prepare", "gcn.dispatch.merge",
             "gcn.dispatch.upload", "gcn.dispatch.launch",
             "gcn.dispatch.answer")
PROGRAM_SPANS = HOST_WORK + (DISPATCH, WAIT, PLAN_BUILD)
OUTSIDE = "outside program spans"


def change(run, key: str) -> Optional[float]:
    """Change of an engine counter across the window, or None where the
    program has no such counter."""
    if key not in run.stats0 or key not in run.stats1:
        return None
    return run.counter(key)


def per_dispatch_ms(run, key: str) -> Optional[float]:
    """Milliseconds of a summed-seconds counter per fused dispatch in the
    window."""
    seconds = change(run, key)
    n = change(run, "batches_dispatched")
    return 1e3 * seconds / n if seconds is not None and n else None


def _program_spans(summary) -> List[Tuple[float, float, str]]:
    out = []
    for h in summary.host:
        if h.name in PROGRAM_SPANS:
            s, e = max(h.start_ns, summary.t0), min(h.end_ns, summary.t1)
            if e > s:
                out.append((s, e, h.name))
    return out


def _idle(summary, plane: str) -> List[Tuple[float, float]]:
    gaps, last = [], summary.t0
    for s, e in summary.busy[plane] + [(summary.t1, summary.t1)]:
        if s > last:
            gaps.append((last, s))
        last = max(last, e)
    return gaps


def _split_plane(idle: List[Tuple[float, float]],
                 spans: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of ``idle`` under each innermost span, by a sweep over
    every boundary."""
    events = []                  # (time, order, kind, index)
    for k, (s, e, _) in enumerate(spans):
        events += [(s, 1, "open", k), (e, 0, "close", k)]
    for s, e in idle:
        events += [(s, 1, "idle", -1), (e, 0, "busy", -1)]
    events.sort()
    out: Dict[str, float] = defaultdict(float)
    active: Dict[int, Tuple[float, str]] = {}
    idle_now, last = False, None
    for t, _, kind, k in events:
        if idle_now and t > last:
            name = (min(active.values())[1] if active else OUTSIDE)
            out[name] += t - last
        last = t
        if kind == "open":
            s, e, name = spans[k]
            active[k] = (e - s, name)
        elif kind == "close":
            del active[k]
        else:
            idle_now = kind == "idle"
    return out


def idle_split(summary) -> Optional[Dict[str, float]]:
    """Seconds the devices sat idle in the window, by innermost program span
    (``OUTSIDE`` for none), averaged over the devices traced; None where no
    program span lies in the window."""
    spans = _program_spans(summary)
    if not spans:
        return None
    total: Dict[str, float] = defaultdict(float)
    for p in summary.planes:
        for name, ns in _split_plane(_idle(summary, p), spans).items():
            total[name] += ns / 1e9 / len(summary.planes)
    return dict(total)
