"""The readers of the program's spans and counters, on hand-made events and
counters: each value against a hand-computed case, and nothing where the
program records nothing (a program without the spans or counters)."""
import math
from pathlib import Path

import pytest

from bench import harness, loader
from bench.spans import OUTSIDE, PLAN_BUILD, PROGRAM_SPANS, idle_split
from bench.trace import (HOST_PLANE, OPS_LINE, Event, TraceSummary,
                         load_events)

MS = 1e6
SPEC = harness.load_spec()
RECORDED = (Path(__file__).resolve().parent / "data"
            / "arxiv_forward_spans.json.gz")
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
NEW_READERS = ["queue_wait_ms.full", "merge_ms.full", "upload_ms.full",
               "answer_ms.full", "plan_build_s.setup", "idle_in_program.full"]


def host(name, start_ms, end_ms, line="python"):
    return Event(HOST_PLANE, line, name, start_ms * MS,
                 (end_ms - start_ms) * MS)


def events(second_device_busy=False):
    """A 100 ms window; the device runs 10..20 and 60..80 (70 ms idle).
    The flush thread holds 0..5 and dispatches 5..90; the caller's frames
    and the harness's spans sit on other lines and are not program spans."""
    evs = [
        host("bench.window", 0, 100, "main"),
        host("bench.aggregate", 0, 95, "main"),
        host("$threading.py:1 run", 0, 100),
        host("sched.hold", 0, 5),
        host("gcn.dispatch", 5, 90),
        host("gcn.dispatch.prepare", 5, 8),
        host("gcn.dispatch.merge", 8, 30),
        host("gcn.dispatch.upload", 30, 35),
        host("gcn.dispatch.launch", 35, 40),
        host("gcn.dispatch.wait", 40, 80),
        host("gcn.dispatch.answer", 80, 85),
        Event(DEV0, OPS_LINE, "custom-call", 10 * MS, 10 * MS),
        Event(DEV0, OPS_LINE, "gather", 60 * MS, 20 * MS),
    ]
    if second_device_busy:
        evs.append(Event(DEV1, OPS_LINE, "fusion", 0, 100 * MS))
    return evs


SPLIT_MS = {"sched.hold": 5, "gcn.dispatch.prepare": 3,
            "gcn.dispatch.merge": 12, "gcn.dispatch.upload": 5,
            "gcn.dispatch.launch": 5, "gcn.dispatch.wait": 20,
            "gcn.dispatch.answer": 5, "gcn.dispatch": 5, OUTSIDE: 10}

STATS0 = {"batches_dispatched": 10, "dispatch_merge_s": 1.0,
          "dispatch_upload_s": 0.1, "dispatch_answer_s": 0.2,
          "sched_queue_wait_s": 0.5, "sched_items_flushed": 30,
          "sched_mid_flush_admissions": 0, "cache_build_s": 2.5}
STATS1 = {"batches_dispatched": 14, "dispatch_merge_s": 1.2,
          "dispatch_upload_s": 0.14, "dispatch_answer_s": 0.24,
          "sched_queue_wait_s": 0.524, "sched_items_flushed": 42,
          "sched_mid_flush_admissions": 0, "cache_build_s": 2.5}
EXPECTED = {"queue_wait_ms.full": 2.0, "merge_ms.full": 50.0,
            "upload_ms.full": 10.0, "answer_ms.full": 10.0,
            "plan_build_s.setup": 2.5, "idle_in_program.full": 35.0}
PARENT_STATS = {"batches_dispatched": 10, "total_serve_s": 1.0,
                "sched_items_flushed": 30, "sched_mid_flush_admissions": 0,
                "cache_builds": 1}


def run_of(evs=None, stats0=STATS0, stats1=STATS1):
    trace = TraceSummary(evs) if evs is not None else None
    return harness.Run(stats0=dict(stats0), stats1=dict(stats1), trace=trace)


def read(name, run):
    return loader.load("metrics", name).read(run)


def test_idle_split_names_each_idle_stretch_by_its_innermost_span():
    split = idle_split(TraceSummary(events()))
    assert split == pytest.approx({k: v / 1e3 for k, v in SPLIT_MS.items()})


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_on_a_hand_computed_case(name):
    assert read(name, run_of(events())) == pytest.approx(EXPECTED[name])


def test_idle_split_averages_over_devices():
    """A second device busy all window halves every row."""
    run = run_of(events(second_device_busy=True))
    assert read("idle_in_program.full", run) == pytest.approx(17.5)
    assert read("device_idle.full", run) == pytest.approx(35.0)


def test_queue_wait_counts_items_taken_into_a_running_flush():
    stats1 = dict(STATS1, sched_mid_flush_admissions=12)
    assert read("queue_wait_ms.full", run_of(stats1=stats1)) == \
        pytest.approx(1.0)


@pytest.mark.parametrize("second_device_busy", [False, True])
def test_split_rows_sum_to_device_idle(second_device_busy, capsys):
    run = run_of(events(second_device_busy))
    idle_in_program = read("idle_in_program.full", run)
    rows = {}
    for line in capsys.readouterr().err.splitlines():
        if line.startswith("[bench] device idle under "):
            name, share = line[len("[bench] device idle under "):].rsplit(
                ": ", 1)
            rows[name] = float(share.rstrip("%"))
    assert set(rows) == set(SPLIT_MS)
    assert sum(rows.values()) == pytest.approx(
        read("device_idle.full", run), abs=1e-2)
    work = [n for n in rows if n not in ("gcn.dispatch", "gcn.dispatch.wait",
                                         OUTSIDE)]
    assert sum(rows[n] for n in work) == pytest.approx(idle_in_program,
                                                       abs=1e-2)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_gives_nothing_without_its_input(name):
    """No trace, and the parent's counters, which lack the new keys."""
    assert read(name, run_of(None, PARENT_STATS, PARENT_STATS)) is None


def test_trace_without_program_spans_gives_nothing():
    evs = [e for e in events() if e.plane != HOST_PLANE
           or e.name.startswith(("bench.", "$"))]
    assert read("idle_in_program.full", run_of(evs)) is None
    assert read("device_idle.full", run_of(evs)) == pytest.approx(70.0)


def test_no_dispatch_in_the_window_gives_nothing():
    stats1 = dict(STATS0)
    for name in ("merge_ms.full", "upload_ms.full", "answer_ms.full",
                 "queue_wait_ms.full"):
        assert read(name, run_of(stats1=stats1)) is None


def test_recorded_pass_with_program_spans():
    """One 3-layer forward pass over the Arxiv analogue with the program's
    spans, traced on a TPU v5e (``data/arxiv_forward_spans.json.gz``, made
    by ``bench/record_trace.py``): every device-trace reader of the spans
    gives a finite value, and the split covers the idle share."""
    summary = TraceSummary(load_events(str(RECORDED)))
    run = harness.Run(stats0={}, stats1={}, trace=summary)
    names = [e.name for e in summary.host if e.name in PROGRAM_SPANS]
    assert len(names) == 27                    # 3 dispatches and 3 holds
    assert set(names) == set(PROGRAM_SPANS) - {PLAN_BUILD}
    for m in SPEC["per_layer"]:
        if m["source"] == "device_trace" and m["name"] in NEW_READERS:
            assert math.isfinite(read(m["name"], run)), m["name"]
    idle = idle_split(summary)
    assert sum(idle.values()) == pytest.approx(summary.window_s
                                               - summary.busy_s())
    assert max(idle, key=idle.get) == "gcn.dispatch.merge"
