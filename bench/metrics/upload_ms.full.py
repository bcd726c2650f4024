"""Host-to-device copy of the merged slabs per fused dispatch: the engine's
``dispatch_upload_s`` (the ``gcn.dispatch.upload`` span) over its
dispatches, both as changes across the window."""
from bench.spans import per_dispatch_ms

UNIT = "ms"
MOVES = "forward_device_ms"


def read(run):
    return per_dispatch_ms(run, "dispatch_upload_s")
