"""Host slab merge per fused dispatch: the engine's ``dispatch_merge_s``
(the ``gcn.dispatch.merge`` span, ``batch_graph_slabs``) over its
dispatches, both as changes across the window."""
from bench.spans import per_dispatch_ms

UNIT = "ms"
MOVES = "forward_device_ms"


def read(run):
    return per_dispatch_ms(run, "dispatch_merge_s")
