"""Plain float32 reference of a GCN forward pass and of one aggregation.

Straightforward ``jax.numpy`` with no kernels, partitions, padding or
batching, written from the GCN equations (Kipf and Welling, 2017):
``H_{l+1} = A' (H_l W_l)``, with ReLU between layers and none after the
last, and ``A'`` applied as a CSR sum over each row's nonzeros. It imports
nothing of the program under test.

``precision="highest"`` is the configuration's float32: elementwise f32
products and HIGHEST-precision matmuls. ``precision="high"`` is the control
one step below it, the TPU's three-pass bfloat16 ("bf16_3x") arithmetic,
emulated explicitly so that it means the same on every backend: each
operand is split into a bfloat16 high part and a bfloat16 low part, and a
product keeps hi*hi + hi*lo + lo*hi, dropping lo*lo.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")
_HIGHEST = jax.lax.Precision.HIGHEST


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _mul(a, b, precision: str):
    if precision == "highest":
        return a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    return ah * bh + (ah * bl + al * bh)


def matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.dot(a, b, precision=_HIGHEST)
    ah, al = _split(a)
    bh, bl = _split(b)
    return (jnp.dot(ah, bh, precision=_HIGHEST)
            + (jnp.dot(ah, bl, precision=_HIGHEST)
               + jnp.dot(al, bh, precision=_HIGHEST)))


@partial(jax.jit, static_argnames=("n_rows", "precision"))
def _spmm(row_of, colidx, values, x, n_rows: int, precision: str):
    contrib = _mul(values[:, None], x[colidx], precision)
    return jax.ops.segment_sum(contrib, row_of, num_segments=n_rows,
                               indices_are_sorted=True)


def spmm(graph, x, precision: str = "highest"):
    """``A' @ x`` for a CSR ``(rowptr, colidx, values)``; float32 result."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    rowptr, colidx, values = graph
    n = len(rowptr) - 1
    row_of = np.repeat(np.arange(n, dtype=np.int32), np.diff(rowptr))
    return _spmm(jnp.asarray(row_of), jnp.asarray(colidx, jnp.int32),
                 jnp.asarray(values, jnp.float32),
                 jnp.asarray(x, jnp.float32), n, precision)


def gcn_forward(graph, x, weights: Sequence, precision: str = "highest"):
    """Logits of the GCN: ``A'(H W)`` per layer, ReLU between layers."""
    h = jnp.asarray(x, jnp.float32)
    for i, w in enumerate(weights):
        h = spmm(graph, matmul(h, jnp.asarray(w, jnp.float32), precision),
                 precision)
        if i < len(weights) - 1:
            h = jax.nn.relu(h)
    return h
