"""The SpMM kernels' share of their roofline: the compulsory time of the
window's aggregations (``work.spmm_work``: A' in CSR, X read once, Y written
once, 2*nnz*F FLOPs, at the request's own width) over the device time of
the XLA modules of the three SpMM entry jits in the trace. Each module
holds its Pallas kernel and the shared ``scatter_block_rows`` epilogue."""
import sys

from bench.trace import NoMatchingEvents
from bench.work import compulsory_s

UNIT = "%"
MOVES = "forward_ms"
MODULES = r"_spmm_block_slabs(_windowed|_hbm)?\b"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    try:
        device_s = run.trace.module_seconds(MODULES)
    except NoMatchingEvents as e:
        print(f"[bench] spmm_roofline: {e}", file=sys.stderr)
        return None
    return 100.0 * compulsory_s(run.work["spmm"], run.peaks) / device_s
