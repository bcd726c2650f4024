"""The whole forward pass's share of the chip's peak: the compulsory time of
the window's passes (three aggregations and three dense ``H W`` layers,
``work.py``) over the window's host-clock time."""
from bench.work import compulsory_s

UNIT = "%"
MOVES = "forward_ms"


def read(run):
    if run.peaks is None:
        return None
    work = run.work["spmm"] + run.work["dense"]
    return 100.0 * compulsory_s(work, run.peaks) / (run.window_s * run.chips)
