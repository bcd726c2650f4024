"""Device time per forward pass of the per-call attention values: the
modules of ``_edge_softmax_slabs`` (edge scores, LeakyReLU and the softmax
over each row's slots) and ``_to_plan_order`` (el and er to the plan's row
order) in the window, over the passes completed in it. A program without
those modules reads nothing."""
import sys

from bench.trace import NoMatchingEvents

UNIT = "ms"
MOVES = "forward_device_ms"
MODULES = r"_(edge_softmax_slabs|to_plan_order)\b"


def read(run):
    if run.trace is None or not run.units:
        return None
    try:
        device_s = run.trace.module_seconds(MODULES)
    except NoMatchingEvents as e:
        print(f"[bench] edge_softmax_ms: {e}", file=sys.stderr)
        return None
    return 1e3 * device_s / run.units
