"""Row-gather passes per block of an ``hbm`` dispatch: the engine's
``hbm_gather_passes`` (F_pad / W per ``hbm`` dispatch, W the kernel's
gather width) over its ``routed_hbm``, both as changes across the window.
1.0 where every block gathers its rows once, at full width."""
from bench.spans import change

UNIT = "passes"
MOVES = "forward_device_ms"


def read(run):
    passes = change(run, "hbm_gather_passes")
    n = change(run, "routed_hbm")
    return passes / n if passes is not None and n else None
