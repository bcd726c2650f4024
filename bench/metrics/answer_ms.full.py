"""Un-permute and per-request slicing of the answers per fused dispatch: the
engine's ``dispatch_answer_s`` (the ``gcn.dispatch.answer`` span) over its
dispatches, both as changes across the window."""
from bench.spans import per_dispatch_ms

UNIT = "ms"
MOVES = "forward_device_ms"


def read(run):
    return per_dispatch_ms(run, "dispatch_answer_s")
