"""BatchScheduler semantics: flush triggers, backpressure, failure scoping."""
import threading
import time

import pytest

from repro.serve.scheduler import BatchScheduler, QueueFullError, percentile


class Recorder:
    """flush_fn that completes every item and records the batches."""

    def __init__(self, delay_s=0.0, gate=None):
        self.batches = []
        self.delay_s = delay_s
        self.gate = gate          # optional Event the flush waits on
        self.entered = threading.Event()  # set when a flush begins

    def __call__(self, items):
        self.entered.set()
        if self.gate is not None:
            assert self.gate.wait(10.0), "test gate never opened"
        if self.delay_s:
            time.sleep(self.delay_s)
        self.batches.append([it.payload for it in items])
        for it in items:
            it.complete(("done", it.payload))


def test_size_trigger_flushes_full_batches():
    rec = Recorder()
    sched = BatchScheduler(rec, max_batch=4, max_wait_ms=10_000, max_queue=64)
    with sched:
        items = sched.submit_many(list(range(8)))
        results = [it.future.result(timeout=10) for it in items]
    assert results == [("done", i) for i in range(8)]
    assert [len(b) for b in rec.batches] == [4, 4]
    st = sched.stats()
    assert st["flush_size"] == 2 and st["completed"] == 8
    assert st["items_per_flush"] == 4.0


def test_deadline_flush_fires_for_single_request():
    """A lone queued request must not wait for co-batchable traffic."""
    rec = Recorder()
    sched = BatchScheduler(rec, max_batch=64, max_wait_ms=30, max_queue=8)
    t0 = time.perf_counter()
    item = sched.submit("solo")
    assert item.future.result(timeout=10) == ("done", "solo")
    elapsed = time.perf_counter() - t0
    assert elapsed < 5.0
    st = sched.stats()
    assert st["flush_deadline"] == 1 and st["flush_size"] == 0
    assert item.latency_s is not None and item.latency_s >= 0.030 * 0.5
    sched.stop()


def test_backpressure_raises_nonblocking_and_times_out():
    gate = threading.Event()
    rec = Recorder(gate=gate)
    sched = BatchScheduler(rec, max_batch=1, max_wait_ms=0, max_queue=2)
    # first item enters the (gated) flush; once the worker is inside it,
    # nothing drains the queue, so filling to max_queue is deterministic
    first = sched.submit("a")
    assert rec.entered.wait(10.0)
    while sched.queue_depth() < 2:
        sched.submit("fill", block=False)
    with pytest.raises(QueueFullError):
        sched.submit("overflow", block=False)
    with pytest.raises(QueueFullError):
        sched.submit("overflow", timeout=0.05)
    assert sched.stats()["rejected"] >= 2
    gate.set()                              # drain; admission works again
    assert first.future.result(timeout=10) == ("done", "a")
    ok = sched.submit("after")
    assert ok.future.result(timeout=10) == ("done", "after")
    sched.stop()


def test_blocking_submit_waits_for_room():
    gate = threading.Event()
    rec = Recorder(gate=gate)
    sched = BatchScheduler(rec, max_batch=1, max_wait_ms=0, max_queue=1)
    sched.submit("a")
    assert rec.entered.wait(10.0)   # worker gated: queue can only grow now
    got = []

    def blocked_submit():
        got.append(sched.submit("b", block=True, timeout=10))

    while sched.queue_depth() < 1:
        sched.submit("fill", block=False)
    t = threading.Thread(target=blocked_submit)
    t.start()
    time.sleep(0.05)
    assert not got, "submit must block while the queue is full"
    gate.set()
    t.join(timeout=10)
    assert got and got[0].future.result(timeout=10)[0] == "done"
    sched.stop()


def test_flush_exception_fails_only_that_flush():
    calls = []

    def flaky(items):
        calls.append(len(items))
        if len(calls) == 1:
            raise ValueError("boom")
        for it in items:
            it.complete("ok")

    sched = BatchScheduler(flaky, max_batch=2, max_wait_ms=1, max_queue=8)
    bad = sched.submit_many(["x", "y"])
    for it in bad:
        with pytest.raises(ValueError, match="boom"):
            it.future.result(timeout=10)
    good = sched.submit("z")
    assert good.future.result(timeout=10) == "ok"
    st = sched.stats()
    assert st["failed"] == 2 and st["completed"] == 1
    sched.stop()


def test_unanswered_items_are_failed_not_hung():
    def forgetful(items):
        items[0].complete("answered")   # leaves the rest unanswered

    sched = BatchScheduler(forgetful, max_batch=3, max_wait_ms=1, max_queue=8)
    items = sched.submit_many(["a", "b", "c"])
    assert items[0].future.result(timeout=10) == "answered"
    for it in items[1:]:
        with pytest.raises(RuntimeError, match="without answering"):
            it.future.result(timeout=10)
    sched.stop()


def test_stop_drains_queue():
    rec = Recorder()
    sched = BatchScheduler(rec, max_batch=64, max_wait_ms=60_000, max_queue=64)
    items = sched.submit_many(list(range(5)))
    sched.stop(timeout=10)                # deadline far away: drain flushes
    assert [it.future.result(timeout=1) for it in items] == \
        [("done", i) for i in range(5)]
    assert sched.stats()["flush_drain"] >= 1


def test_take_ready_pulls_into_running_flush():
    sched_box = {}

    def reusing(items):
        for it in items:
            it.complete("first")
        time.sleep(0.05)  # let late submits queue up
        for extra in sched_box["s"].take_ready(8):
            extra.complete("pulled")

    sched = BatchScheduler(reusing, max_batch=1, max_wait_ms=0, max_queue=8)
    sched_box["s"] = sched
    a = sched.submit("a")
    time.sleep(0.01)
    late = [sched.submit(f"late{i}") for i in range(3)]
    assert a.future.result(timeout=10) == "first"
    results = {it.future.result(timeout=10) for it in late}
    assert "pulled" in results            # at least one mid-flush admission
    assert sched.stats()["mid_flush_admissions"] >= 1
    sched.stop()


def test_take_ready_items_fail_with_flush_exception():
    sched_box = {}

    def pull_then_raise(items):
        for it in items:
            it.complete("first")
        deadline = time.perf_counter() + 5
        while not sched_box["s"].take_ready(1):
            if time.perf_counter() > deadline:
                raise AssertionError("late item never arrived")
            time.sleep(0.002)
        raise ValueError("mid-flush boom")

    sched = BatchScheduler(pull_then_raise, max_batch=1, max_wait_ms=0,
                           max_queue=8)
    sched_box["s"] = sched
    a = sched.submit("a")
    assert a.future.result(timeout=10) == "first"
    late = sched.submit("late")
    with pytest.raises(ValueError, match="mid-flush boom"):
        late.future.result(timeout=10)
    sched.stop()


def test_percentile_nearest_rank():
    """Nearest-rank index is ceil(q*n)-1 — the old int(q*n) sat one rank
    high (p50 of [1,2,3,4] came back 3)."""
    assert percentile([], 0.5) == 0.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.0) == 1.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 1.0) == 4.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.75) == 3.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 0.76) == 4.0
    assert percentile([7.0], 0.0) == 7.0
    assert percentile([7.0], 0.5) == 7.0
    assert percentile([7.0], 1.0) == 7.0
    # p99 of 100 sorted values is the 99th (index 98), not the maximum
    vals = [float(i) for i in range(1, 101)]
    assert percentile(vals, 0.99) == 99.0
    assert percentile(vals, 0.5) == 50.0


def test_latency_percentiles_and_validation():
    rec = Recorder()
    sched = BatchScheduler(rec, max_batch=2, max_wait_ms=1, max_queue=8)
    items = sched.submit_many(list(range(6)))
    for it in items:
        it.future.result(timeout=10)
    st = sched.stats()
    assert 0 < st["p50_latency_s"] <= st["p90_latency_s"] <= st["p99_latency_s"]
    assert st["avg_latency_s"] > 0
    sched.stop()
    with pytest.raises(ValueError):
        BatchScheduler(rec, max_batch=0)
    with pytest.raises(ValueError):
        BatchScheduler(rec, max_queue=0)
    with pytest.raises(ValueError):
        BatchScheduler(rec, max_wait_ms=-1)


def test_cancel_mid_flush_does_not_poison_cobatched_requests():
    """A caller cancelling its future while the flush is answering the batch
    must not fail the OTHER items of that flush (the old check-then-set
    window raised InvalidStateError inside the flush callback)."""
    def flush(items):
        # deterministic lost race: the "caller" cancels item 1 after the
        # flush picked up the batch but before it answers anything
        if len(items) > 1:
            items[1].future.cancel()
        for it in items:
            it.complete(("done", it.payload))

    sched = BatchScheduler(flush, max_batch=4, max_wait_ms=10_000, max_queue=8)
    items = sched.submit_many(["a", "b", "c", "d"])
    for i in (0, 2, 3):
        assert items[i].future.result(timeout=10) == ("done", items[i].payload)
    assert items[1].future.cancelled()
    st = sched.stats()
    assert st["cancelled"] == 1
    assert st["completed"] == 3 and st["failed"] == 0
    assert st["completed"] + st["failed"] + st["cancelled"] == st["submitted"]
    sched.stop()


def test_cancel_during_straggler_fail_does_not_kill_worker():
    """The post-flush straggler loop must survive a cancel racing it: a
    flush that leaves items unanswered AND sees them cancelled must not
    leak InvalidStateError out of _worker (which silently killed the
    thread and hung every later submit)."""
    def forgetful(items):
        for it in items[1:]:
            it.future.cancel()      # cancelled AND unanswered stragglers
        items[0].complete("answered")

    sched = BatchScheduler(forgetful, max_batch=3, max_wait_ms=1, max_queue=8)
    items = sched.submit_many(["a", "b", "c"])
    assert items[0].future.result(timeout=10) == "answered"
    for it in items[1:]:
        assert it.future.cancelled()
    # the worker must still be alive to serve this
    again = sched.submit("again")
    assert again.future.result(timeout=10) == "answered"
    st = sched.stats()
    assert st["cancelled"] == 2
    assert st["completed"] + st["failed"] + st["cancelled"] == st["submitted"]
    sched.stop()


def test_cancel_hammer_invariant_and_worker_survival():
    """Hammer thread cancels futures mid-flush while traffic flows: no
    InvalidStateError may escape, every non-cancelled item resolves, and
    completed + failed + cancelled == submitted at quiesce."""
    def flush(items):
        time.sleep(0.001)           # widen the cancel window
        for it in items:
            it.complete(("done", it.payload))

    sched = BatchScheduler(flush, max_batch=4, max_wait_ms=0.5, max_queue=512)
    all_items, items_lock = [], threading.Lock()
    stop_hammer = threading.Event()

    def hammer():
        i = 0
        while not stop_hammer.is_set():
            with items_lock:
                pending = [it for it in all_items if not it.future.done()]
            for it in pending[i % 2::2]:    # alternate halves
                it.future.cancel()
            i += 1
            time.sleep(0.0005)

    hammer_t = threading.Thread(target=hammer)
    hammer_t.start()
    try:
        for _round in range(30):
            items = sched.submit_many(list(range(8)))
            with items_lock:
                all_items.extend(items)
            time.sleep(0.002)
    finally:
        stop_hammer.set()
        hammer_t.join(timeout=10)
    # quiesce: every item must reach a terminal state
    for it in all_items:
        if not it.future.cancelled():
            assert it.future.result(timeout=10)[0] == "done"
    sched.stop(timeout=10)
    st = sched.stats()
    assert st["completed"] + st["failed"] + st["cancelled"] == st["submitted"]
    assert st["failed"] == 0, "cancel races must not fail co-batched items"
    assert st["submitted"] == 240
    # the worker survived the whole hammer session
    again = sched.submit("alive")
    assert again.future.result(timeout=10) == ("done", "alive")
    sched.stop()


def test_submit_many_counts_rejected_items_and_times_out():
    """submit_many parity with submit: a rejected run counts every ITEM in
    `rejected` (not 1 per call), and timeout= raises QueueFullError after
    the deadline instead of blocking forever."""
    gate = threading.Event()
    rec = Recorder(gate=gate)
    sched = BatchScheduler(rec, max_batch=1, max_wait_ms=0, max_queue=2)
    first = sched.submit("a")
    assert rec.entered.wait(10.0)   # worker gated: queue can only grow
    while sched.queue_depth() < 2:
        sched.submit("fill", block=False)
    with pytest.raises(QueueFullError):
        sched.submit_many(["x", "y", "z"], block=False)
    assert sched.stats()["rejected"] == 3
    t0 = time.perf_counter()
    with pytest.raises(QueueFullError):
        sched.submit_many(["x", "y"], timeout=0.05)
    assert time.perf_counter() - t0 < 5.0
    assert sched.stats()["rejected"] == 5
    gate.set()
    assert first.future.result(timeout=10) == ("done", "a")
    items = sched.submit_many(["p", "q"], timeout=10)
    for it in items:
        assert it.future.result(timeout=10)[0] == "done"
    sched.stop()


def test_restart_after_stop():
    """A stopped scheduler restarts transparently on the next submit —
    items can never sit in a queue with no worker to drain them."""
    rec = Recorder()
    sched = BatchScheduler(rec, max_batch=2, max_wait_ms=1, max_queue=8)
    a = sched.submit("a")
    assert a.future.result(timeout=10) == ("done", "a")
    sched.stop(timeout=10)
    b = sched.submit("b")
    assert b.future.result(timeout=10) == ("done", "b")
    sched.stop(timeout=10)


# ------------------------------------------------------------- SLO classes
def test_interactive_class_jumps_batch_backlog_and_meets_deadline():
    """A deadline-bearing interactive request submitted behind a full
    batch-class backlog rides the very next flush (priority order), not
    the end of the queue — so its SLO holds under batch pressure."""
    from repro.serve.scheduler import ClassSpec

    gate = threading.Event()
    rec = Recorder(gate=gate)
    sched = BatchScheduler(
        rec, max_batch=4, max_wait_ms=10_000, max_queue=64,
        classes=[ClassSpec("batch", priority=0, weight=1.0),
                 ClassSpec("interactive", priority=10, weight=1.0,
                           deadline_ms=5_000)])
    with sched:
        batch_items = sched.submit_many([f"b{i}" for i in range(12)],
                                        klass="batch")
        # first flush (4 batch items) is now blocked on the gate; the
        # other 8 batch items sit queued ahead of the interactive arrival
        assert rec.entered.wait(10.0)
        hot = sched.submit("hot", klass="interactive")
        gate.set()
        assert hot.future.result(timeout=10) == ("done", "hot")
    # "hot" took a slot of a batch flush, so the last batch item is left
    # alone in the queue; leaving the block stopped the scheduler, whose
    # drain flushes it there, not on the max_wait_ms deadline, so no clock
    # decides this test
    for it in batch_items:
        it.future.result(timeout=0)
    # the interactive item outran the 8 queued batch items: it is in the
    # flush right after the gated one
    assert "hot" in rec.batches[1]
    assert hot.deadline_missed is False
    st = sched.stats()
    assert st["class_completed"]["interactive"] == 1
    assert st["class_deadline_missed"]["interactive"] == 0
    assert st["per_class_p99"]["interactive"] > 0.0


def test_slo_class_flushes_early_without_cobatch_traffic():
    """A lone deadline-class request flushes after ~deadline/4, not after
    the scheduler-wide max_wait."""
    from repro.serve.scheduler import ClassSpec

    rec = Recorder()
    sched = BatchScheduler(
        rec, max_batch=64, max_wait_ms=30_000, max_queue=8,
        classes=[ClassSpec("interactive", priority=1, deadline_ms=200)])
    t0 = time.perf_counter()
    item = sched.submit("solo", klass="interactive")
    assert item.future.result(timeout=10) == ("done", "solo")
    assert time.perf_counter() - t0 < 10.0   # not the 30s scheduler wait
    st = sched.stats()
    assert st["flush_slo"] == 1
    assert item.deadline_at is not None
    sched.stop()


def test_weighted_fair_admission_caps_lower_tier_only():
    """A lower-priority flood hits its weighted quota and backpressures
    while the top tier still admits freely."""
    from repro.serve.scheduler import ClassSpec

    gate = threading.Event()
    rec = Recorder(gate=gate)
    sched = BatchScheduler(
        rec, max_batch=4, max_wait_ms=10_000, max_queue=9,
        classes=[ClassSpec("batch", priority=0, weight=1.0),
                 ClassSpec("interactive", priority=10, weight=1.0)])
    with sched:
        # park the flush thread on 4 default-class items so later
        # submissions stay queued
        parked = sched.submit_many(list(range(4)))
        assert rec.entered.wait(10.0)
        # quota for the lower tier: max_queue * w/total_w = 9/3 = 3
        flood = [sched.submit(f"b{i}", klass="batch", block=False)
                 for i in range(3)]
        with pytest.raises(QueueFullError):
            sched.submit("b3", klass="batch", block=False)
        # the top tier is NOT capped by the flood
        hot = sched.submit("hot", klass="interactive", block=False)
        gate.set()
        for it in parked + flood + [hot]:
            it.future.result(timeout=10)
    st = sched.stats()
    assert st["rejected"] == 1
    assert st["class_completed"]["batch"] == 3
    assert st["class_completed"]["interactive"] == 1
