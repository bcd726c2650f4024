"""Host dispatch time per fused dispatch: the engine's summed dispatch wall
time (``total_serve_s``, merge to ``block_until_ready``) over its
dispatches, both as changes across the window."""
UNIT = "ms"
MOVES = "forward_device_ms"


def read(run):
    n = run.counter("batches_dispatched")
    return 1e3 * run.counter("total_serve_s") / n if n else None
