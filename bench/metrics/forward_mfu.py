"""The whole forward pass's share of the chip's peak: the compulsory time of
all the work of the window's passes, every kind the model file's ``work()``
reports (for ``gcn`` its aggregations and its dense ``H W`` layers,
``work.py``), over the window's host-clock time. Each model file's
``work()`` defines the compulsory work of its pass."""
from bench.work import Work, compulsory_s

UNIT = "%"
MOVES = "forward_device_ms"


def read(run):
    if run.peaks is None:
        return None
    work = sum(run.work.values(), Work())
    return 100.0 * compulsory_s(work, run.peaks) / (run.window_s * run.chips)
