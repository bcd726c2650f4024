"""Host time to launch the per-call attention values per attention
dispatch: the engine's ``dispatch_scores_s`` (the ``gcn.dispatch.scores``
span) over its ``attn_dispatches``, both as changes across the window. A
program without those counters reads nothing."""
from bench.spans import change

UNIT = "ms"
MOVES = "forward_device_ms"


def read(run):
    seconds = change(run, "dispatch_scores_s")
    n = change(run, "attn_dispatches")
    return 1e3 * seconds / n if seconds is not None and n else None
