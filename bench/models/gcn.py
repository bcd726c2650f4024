"""Model kind ``gcn``: an L-layer GCN, ``H_{l+1} = A' (H_l W_l)`` with ReLU
between layers and none after the last (Kipf and Welling, 2017), its widths
``config["model"]["widths"]``. Each aggregation is one request to the
program's engine; the dense ``H W`` and the ReLU run on the device between
them. Its plain reference is ``bench/references/gcn.py``.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.data import CSR
from bench.references import gcn as reference
from bench.system import jax_seed
from bench.work import Work, dense_work, spmm_work

_dense = jax.jit(partial(jnp.dot, precision=jax.lax.Precision.HIGHEST))
_relu = jax.jit(jax.nn.relu)


def prepare(g: CSR) -> CSR:
    """A' = D^-1/2 (A + I) D^-1/2 with D the row degrees of A + I: a self
    loop closes every row, values computed in float64, stored float32. A
    copy of the repository's normalisation, kept here so that a later
    change to the program's data code cannot move the yardstick."""
    rowptr, colidx, values = g
    n = len(rowptr) - 1
    deg = np.diff(rowptr)
    new_rowptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg + 1, out=new_rowptr[1:])
    keep = np.ones(int(new_rowptr[-1]), dtype=bool)
    keep[new_rowptr[1:] - 1] = False              # last slot of each row
    cols = np.empty(int(new_rowptr[-1]), dtype=np.int64)
    vals = np.empty(int(new_rowptr[-1]), dtype=np.float64)
    cols[keep], vals[keep] = colidx, values
    cols[~keep], vals[~keep] = np.arange(n), 1.0
    dinv = 1.0 / np.sqrt(np.diff(new_rowptr).astype(np.float64))
    row_of = np.repeat(np.arange(n), np.diff(new_rowptr))
    vals = vals * dinv[row_of] * dinv[cols]
    return new_rowptr, cols, vals.astype(np.float32)


def make_inputs(config: Dict, n: int, sets: int, seed: int):
    """``sets`` feature matrices ``[sets, n, widths[0]]``, N(0, 1), and the
    weights, N(0, 2/(f_in+f_out)), in one jitted call from the seed."""
    widths = list(config["model"]["widths"])

    @jax.jit
    def make(key):
        kx, *kw = jax.random.split(key, len(widths))
        xs = jax.random.normal(kx, (sets, n, widths[0]), jnp.float32)
        ws = [jax.random.normal(k, (fi, fo), jnp.float32)
              * jnp.sqrt(2.0 / (fi + fo))
              for k, fi, fo in zip(kw, widths[:-1], widths[1:])]
        return xs, ws

    return make(jax.random.key(jax_seed(seed, 2)))


def forward(engine, graph_id: str, params: Sequence, x) -> jax.Array:
    """One pass: per layer the dense ``H W``, one aggregation through the
    engine, and the ReLU between layers."""
    h = x
    for i, w in enumerate(params):
        with jax.profiler.TraceAnnotation("bench.dense"):
            hw = _dense(h, w)
        with jax.profiler.TraceAnnotation("bench.aggregate"):
            h = engine.submit(graph_id, hw).result()
        if i < len(params) - 1:
            h = _relu(h)
    return jax.block_until_ready(h)


def work(graph: CSR, config: Dict) -> Dict[str, Work]:
    """Compulsory work of one pass: its aggregations and its dense layers."""
    n, nnz = len(graph[0]) - 1, len(graph[1])
    widths = config["model"]["widths"]
    spmm = dense = Work()
    for fi, fo in zip(widths[:-1], widths[1:]):
        dense = dense + dense_work(n, fi, fo)
        spmm = spmm + spmm_work(nnz, n, n, fo)
    return {"spmm": spmm, "dense": dense}


def reference_pairs(graph: CSR, params: Sequence, x, precision: str):
    """``(ref, terms)``: the plain forward pass's logits, and the number of
    terms each row of the last aggregation sums."""
    ref = reference.gcn_forward(graph, x, params, precision)
    return np.asarray(ref), np.diff(graph[0])


def tiny(config: Dict) -> Dict:
    """The configuration at a size a CPU test holds: 3,000 nodes and
    widths 16-32-8."""
    graph = dict(config["graph"], nodes=3000, edges=20000, max_degree=300)
    return dict(config, graph=graph,
                model=dict(config["model"], widths=[16, 32, 8]))
