"""Slab rows the SpMM epilogue reads per answer row: the engine's
``epilogue_rows`` (n_out + B of each dispatch, the rows
``scatter_block_rows`` reads) over its ``rows_served``, both as changes
across the window. About 1 + B / n_rows where each kept row is read once;
a scatter-add of every slot would read B * R / n_rows."""
from bench.spans import change

UNIT = "rows/row"
MOVES = "forward_device_ms"


def read(run):
    reads = change(run, "epilogue_rows")
    rows = change(run, "rows_served")
    return reads / rows if reads is not None and rows else None
