"""Scheduler queue wait per request: the summed wait from enqueue to the
flush that took each item (``sched_queue_wait_s``, items taken into a
running flush included) over the items taken, as changes across the
window."""
from bench.spans import change

UNIT = "ms"
MOVES = "forward_device_ms"


def read(run):
    wait = change(run, "sched_queue_wait_s")
    items = change(run, "sched_items_flushed")
    extra = change(run, "sched_mid_flush_admissions") or 0.0
    if wait is None or not items:
        return None
    return 1e3 * wait / (items + extra)
