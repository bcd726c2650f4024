"""The trace reduction, on hand-made events and on a small recorded trace."""
from pathlib import Path

import pytest

from bench import harness, loader
from bench.trace import (HOST_PLANE, MODULES_LINE, OPS_LINE, Event,
                         NoMatchingEvents, TraceSummary, load_events)

DEV = "/device:TPU:0"
RECORDED = Path(__file__).resolve().parent / "data"


def synthetic():
    ms = 1e6
    return [
        Event(HOST_PLANE, "python", "bench.window", 0, 100 * ms),
        Event(HOST_PLANE, "python", "bench.dense", 5 * ms, 10 * ms),
        Event(HOST_PLANE, "python", "bench.aggregate", 40 * ms, 50 * ms),
        Event(DEV, OPS_LINE, "fusion.1", 10 * ms, 10 * ms),     # 10..20
        Event(DEV, OPS_LINE, "fusion.2", 15 * ms, 10 * ms),     # 15..25
        Event(DEV, OPS_LINE, "custom-call", 60 * ms, 20 * ms),  # 60..80
        Event(DEV, OPS_LINE, "late", 95 * ms, 10 * ms),         # clipped
        Event(DEV, MODULES_LINE, "jit_dense(1)", 10 * ms, 15 * ms),
        Event(DEV, MODULES_LINE, "jit__spmm_block_slabs_hbm(2)", 60 * ms,
              20 * ms),
    ]


def test_busy_union_and_idle_share():
    s = TraceSummary(synthetic())
    assert s.window_s == pytest.approx(0.1)
    # 10..25 + 60..80 + 95..100 = 40 ms busy
    assert s.busy_s() == pytest.approx(0.040)
    assert s.idle_share() == pytest.approx(0.6)


def test_module_seconds_and_missing_modules():
    s = TraceSummary(synthetic())
    assert s.module_seconds(r"_spmm_block_slabs(_windowed|_hbm)?\b") == \
        pytest.approx(0.020)
    with pytest.raises(NoMatchingEvents):
        s.module_seconds(r"no_such_kernel")


def test_idle_gaps_are_named_by_the_host_event_over_them():
    gaps = TraceSummary(synthetic()).idle_gaps(3)
    assert gaps[0] == ["bench.aggregate", pytest.approx(0.035)]  # 25..60
    assert gaps[1][1] == pytest.approx(0.015)                     # 80..95
    assert gaps[2] == ["bench.dense", pytest.approx(0.010)]      # 0..10


def test_a_trace_without_device_ops_is_an_error():
    events = [e for e in synthetic() if e.plane == HOST_PLANE]
    with pytest.raises(NoMatchingEvents):
        TraceSummary(events)


def test_recorded_forward_pass():
    """One 3-layer forward pass over the Arxiv analogue, traced on a TPU v5e
    (``data/arxiv_forward_trace.json.gz``, cut to that pass): the two
    ``hbm`` SpMM programs (F=256 and F=40) take most of the device time."""
    s = TraceSummary(load_events(str(RECORDED / "arxiv_forward_trace.json.gz")))
    assert s.planes == ["/device:TPU:0"]
    assert s.window_s == pytest.approx(0.640228303)
    assert s.busy_s() == pytest.approx(0.471375075)
    spmm = s.module_seconds(r"_spmm_block_slabs(_windowed|_hbm)?\b")
    assert spmm == pytest.approx(0.463733713)
    top = s.top_modules(2)
    assert all(name.startswith("jit__spmm_block_slabs_hbm") for name, _ in top)
    gaps = s.idle_gaps(3)
    assert gaps[0] == ["$_arraypad_impl.py:86 _pad_simple",
                       pytest.approx(0.04908986)]


def test_forward_device_ms_is_busy_time_per_pass():
    """``forward_device_ms`` on the recorded pass: its device busy time; and
    nothing without a trace."""
    s = TraceSummary(load_events(str(RECORDED / "arxiv_forward_trace.json.gz")))
    reader = loader.load("metrics", "forward_device_ms")
    assert reader.read(harness.Run(trace=s, units=1)) == \
        pytest.approx(471.375075)
    assert reader.read(harness.Run(trace=s, units=2)) == \
        pytest.approx(235.6875375)
    assert reader.read(harness.Run(trace=None, units=1)) is None


def test_forward_ms_wall_is_the_loops_pass_time():
    reader = loader.load("metrics", "forward_ms.wall")
    assert reader.read(harness.Run(end_to_end={"forward_ms": 470.5})) == 470.5
    assert reader.read(harness.Run(end_to_end={})) is None
