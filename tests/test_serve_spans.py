"""Spans and phase counters of the served path (``repro.core.spans``).

One request through ``GraphServeEngine.submit`` under ``jax.profiler.trace``
must leave every span on the host plane, the dispatch phases nested in
``gcn.dispatch``; the counters in ``engine.stats()`` must rise with each
dispatch and split ``total_serve_s``."""
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.graph import gcn_normalize
from repro.serve.graph_engine import GraphServeEngine
from repro.serve.scheduler import BatchScheduler

from graph_helpers import make_powerlaw_csr

HOLD_MS = 50.0   # long enough that the worker always holds a lone request
ALL_SPANS = (spans.SCHED_HOLD, spans.DISPATCH, *spans.DISPATCH_PHASES,
             spans.PLAN_BUILD)
# a dispatch of plain requests only has every phase but the attention scores
PLAIN_PHASES = tuple(p for p in spans.DISPATCH_PHASES if p != spans.SCORES)
PLAIN_SPANS = tuple(s for s in ALL_SPANS if s != spans.SCORES)
COUNTERS = ["dispatch_prepare_s", "dispatch_merge_s", "dispatch_upload_s",
            "dispatch_launch_s", "dispatch_wait_s", "dispatch_answer_s",
            "sched_queue_wait_s"]


def _engine():
    return GraphServeEngine(backend="blocked", max_wait_ms=HOLD_MS)


def _graph_and_x(seed=0, n=300, f=16):
    g = gcn_normalize(make_powerlaw_csr(n=n, seed=seed))
    x = np.random.default_rng(seed).normal(size=(n, f)).astype(np.float32)
    return g, x


def _trace(trace_dir, submit):
    """Host-plane events of one register, then ``submit(engine, g, x)``
    under ``jax.profiler.trace``, each as ``(line index, name, start_ns,
    end_ns)``."""
    from jax.profiler import ProfileData
    engine = _engine()
    g, x = _graph_and_x()
    try:
        with jax.profiler.trace(trace_dir):
            engine.register_graph("g", g)
            submit(engine, g, x)
            # the future resolves inside gcn.dispatch: let the flush
            # thread close its spans before the trace stops
            engine.close()
    finally:
        engine.close()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    (host,) = [p for p in pd.planes if p.name == "/host:CPU"]
    return [(i, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for i, line in enumerate(host.lines) for ev in line.events
            if ev.name in ALL_SPANS]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One ``submit().result()`` of a plain request."""
    return _trace(str(tmp_path_factory.mktemp("trace")),
                  lambda engine, g, x: engine.submit("g", x).result())


@pytest.fixture(scope="module")
def traced_attention(tmp_path_factory):
    """A plain and an attention request in one flush: one dispatch through
    every phase, ``scores`` included."""
    def submit(engine, g, x):
        scores = np.ones((g.n_rows, 1), np.float32)
        plain = engine.submit("g", x)
        engine.submit("g", x[:, None, :],
                      attention=(scores, scores)).result()
        plain.result()
    return _trace(str(tmp_path_factory.mktemp("trace")), submit)


def _assert_phases_nest(events, phases_expected):
    dispatches = [ev for ev in events if ev[1] == spans.DISPATCH]
    phases = [ev for ev in events if ev[1] in spans.DISPATCH_PHASES]
    assert len(dispatches) == 1 and len(phases) >= len(phases_expected)
    line, _, start, end = dispatches[0]
    for ev in phases:
        assert ev[0] == line and start <= ev[2] <= ev[3] <= end, ev


@pytest.mark.parametrize("name", PLAIN_SPANS)
def test_span_is_on_the_host_plane(traced, name):
    assert any(ev[1] == name for ev in traced), name


def test_plain_dispatch_has_no_scores_span(traced):
    assert not any(ev[1] == spans.SCORES for ev in traced)


@pytest.mark.parametrize("name", ALL_SPANS)
def test_span_of_an_attention_dispatch_is_on_the_host_plane(
        traced_attention, name):
    assert any(ev[1] == name for ev in traced_attention), name


def test_dispatch_phases_nest_inside_the_dispatch_span(traced):
    _assert_phases_nest(traced, PLAIN_PHASES)


def test_attention_dispatch_phases_nest_inside_the_dispatch_span(
        traced_attention):
    _assert_phases_nest(traced_attention, spans.DISPATCH_PHASES)


def test_phase_counters_rise_and_split_total_serve_s():
    engine = _engine()
    g, x = _graph_and_x()
    engine.register_graph("g", g)
    try:
        before = engine.stats()
        for k in range(2):
            engine.submit("g", x * (k + 1)).result()
            after = engine.stats()
            for key in COUNTERS:
                assert after[key] > before[key], key
            before = after
    finally:
        engine.close()
    pre_answer = sum(after[f"dispatch_{p}_s"] for p in
                     ("prepare", "merge", "upload", "launch", "wait"))
    assert 0 < pre_answer <= after["total_serve_s"]
    assert after["batches_dispatched"] == 2


def test_queue_wait_covers_the_hold_of_each_flush():
    """A lone request is flushed at its deadline, so each flush adds at
    least ``max_wait_ms`` of queue wait."""
    engine = _engine()
    g, x = _graph_and_x()
    engine.register_graph("g", g)
    try:
        waits = [engine.stats()["sched_queue_wait_s"]]
        for _ in range(2):
            engine.submit("g", x).result()
            waits.append(engine.stats()["sched_queue_wait_s"])
    finally:
        engine.close()
    assert waits[0] == 0.0
    assert all(b - a >= HOLD_MS / 1e3 for a, b in zip(waits, waits[1:]))


def test_queue_wait_counts_take_ready_admissions():
    first_running, second_queued = threading.Event(), threading.Event()
    pulled = []

    def flush(items):
        for it in items:
            if it.payload == "first":
                first_running.set()
                second_queued.wait(5)
                time.sleep(0.05)
                pulled.extend(sched.take_ready(1))
            it.complete(it.payload)
        for it in pulled:
            it.complete(it.payload)

    sched = BatchScheduler(flush, max_batch=1, max_wait_ms=0.0)
    try:
        a = sched.submit("first")
        first_running.wait(5)
        b = sched.submit("second")
        second_queued.set()
        assert a.future.result(5) == "first"
        assert b.future.result(5) == "second"
        st = sched.stats()
    finally:
        sched.stop()
    assert [it.payload for it in pulled] == ["second"]
    assert st["mid_flush_admissions"] == 1
    assert st["queue_wait_s"] >= 0.05


def test_plan_build_seconds_count_builds_not_hits():
    engine = _engine()
    g, _ = _graph_and_x()
    assert engine.stats()["cache_build_s"] == 0.0
    engine.register_graph("g", g)
    built = engine.stats()
    assert built["cache_builds"] == 1 and built["cache_build_s"] > 0
    engine.register_graph("g2", g)           # same content: a cache hit
    engine.plan_for("g")
    again = engine.stats()
    assert again["cache_hits"] >= 2
    assert again["cache_build_s"] == built["cache_build_s"]
    engine.close()


def test_plan_timings_are_gone():
    engine = _engine()
    g, x = _graph_and_x()
    engine.register_graph("g", g)
    engine.submit("g", x).result()
    engine.close()
    assert "plan_timings" not in engine.stats()
    assert not hasattr(engine, "plan_timings")


def test_span_adds_its_seconds_to_the_callers_dict():
    acc = {}
    for _ in range(2):
        with spans.span("probe", acc):
            time.sleep(0.01)
    with spans.span("untimed"):
        pass
    assert set(acc) == {"probe"} and acc["probe"] >= 0.02
