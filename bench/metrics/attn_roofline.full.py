"""The attention kernels' share of their roofline: the compulsory time of
the window's attention aggregations (``attention_work`` of the
``graph_attention`` model: the CSR structure, el and er, z read once and
the answer written once; ``nnz * heads * (2 * F + 4)`` FLOPs) over the
device time of the modules of the per-call values (``_edge_softmax_slabs``,
``_to_plan_order``) and of the three SpMM entry jits in the trace. A run
whose model reports no ``attention`` work, or whose program has no such
modules, reads nothing."""
import sys

from bench.trace import NoMatchingEvents
from bench.work import compulsory_s

UNIT = "%"
MOVES = "forward_device_ms"
MODULES = (r"_(edge_softmax_slabs|to_plan_order"
           r"|spmm_block_slabs(_windowed|_hbm)?)\b")
SCORE_MODULES = r"_(edge_softmax_slabs|to_plan_order)\b"


def read(run):
    if run.trace is None or run.peaks is None or "attention" not in run.work:
        return None
    try:
        run.trace.module_seconds(SCORE_MODULES)
        device_s = run.trace.module_seconds(MODULES)
    except NoMatchingEvents as e:
        print(f"[bench] attn_roofline: {e}", file=sys.stderr)
        return None
    return 100.0 * compulsory_s(run.work["attention"], run.peaks) / device_s
