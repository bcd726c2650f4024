"""Plain float32 reference of a GAT forward pass, as DGL's ogbn-arxiv GAT
example runs it in inference mode.

Straightforward ``jax.numpy`` with no kernels, partitions, padding or
batching, written from the GAT equations (Veličković et al.,
arXiv:1710.10903) and the DGL example's model (``examples/pytorch/ogb/
ogbn-arxiv/models.py``, class ``GAT``), over the prepared CSR: row ``i``'s
nonzeros are the nodes ``j`` it aggregates from. Layer ``l`` with ``H``
heads of width ``F``:

    z = h W_l                       [N, H, F]
    el = sum_f z a_l,  er = sum_f z a_r       [N, H]
    e_ij = LeakyReLU(el_j + er_i),  alpha_ij = softmax over row i's j
    out_i = sum_j alpha_ij z_j + (h R_l)_i
    layers before the last: flatten heads, BatchNorm (eval), ReLU
    the last: mean over heads, plus a bias

It imports nothing of the program under test. The aggregation runs over
blocks of nonzeros, so that at the cell's full size the gathered ``[nnz, H,
F]`` rows never exist at once beside the program's state.

``precision`` is as in ``references/gcn.py``: ``"highest"`` is float32 with
HIGHEST-precision matmuls, ``"high"`` the control, the TPU's three-pass
bfloat16 products (score projections, attention-weighted rows) and matmuls;
the softmax and the BatchNorm stay float32 in both.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.gcn import PRECISIONS, _mul, matmul

NNZ_BLOCK = 1 << 17     # nonzeros aggregated per step
BN_EPS = 1e-5           # torch.nn.BatchNorm1d's default


@partial(jax.jit, static_argnames=("n",))
def _attention(row_of, colidx, el, er, slope, n: int):
    e = el[colidx] + er[row_of]                                 # [nnz, H]
    e = jnp.where(e >= 0, e, slope * e)
    m = jax.ops.segment_max(e, row_of, num_segments=n,
                            indices_are_sorted=True)
    p = jnp.exp(e - m[row_of])
    s = jax.ops.segment_sum(p, row_of, num_segments=n,
                            indices_are_sorted=True)
    return p / s[row_of]


@partial(jax.jit, static_argnames=("n", "precision"), donate_argnums=(0,))
def _aggregate_block(out, row_of, colidx, alpha, z, n: int, precision: str):
    contrib = _mul(alpha[:, :, None], z[colidx], precision)     # [k, H, F]
    return out + jax.ops.segment_sum(contrib, row_of, num_segments=n + 1,
                                     indices_are_sorted=True)


def gat_attention(rowptr, colidx, z, el, er, slope: float,
                  precision: str = "highest"):
    """``out[i, h] = sum_j alpha_ijh z[j, h]`` over row i's nonzeros j."""
    n = len(rowptr) - 1
    nnz = len(colidx)
    row_of = np.repeat(np.arange(n, dtype=np.int32), np.diff(rowptr))
    cols = np.asarray(colidx, np.int32)
    alpha = _attention(jnp.asarray(row_of), jnp.asarray(cols), el, er,
                       jnp.float32(slope), n)
    pad = -nnz % NNZ_BLOCK
    # padded nonzeros add into a spare row n, dropped below
    row_of = np.concatenate([row_of, np.full(pad, n, np.int32)])
    cols = np.concatenate([cols, np.zeros(pad, np.int32)])
    alpha = jnp.concatenate([alpha, jnp.zeros((pad, alpha.shape[1]),
                                              alpha.dtype)])
    out = jnp.zeros((n + 1,) + z.shape[1:], jnp.float32)
    for k in range(0, nnz + pad, NNZ_BLOCK):
        sl = slice(k, k + NNZ_BLOCK)
        out = _aggregate_block(out, jnp.asarray(row_of[sl]),
                               jnp.asarray(cols[sl]), alpha[sl], z, n,
                               precision)
    return out[:n]


def gat_forward(graph, x, params: Dict, slope: float,
                precision: str = "highest"):
    """Logits ``[N, classes]`` of the GAT over the prepared CSR graph."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    rowptr, colidx, _ = graph
    n = len(rowptr) - 1
    h = jnp.asarray(x, jnp.float32)
    layers = params["layers"]
    for i, layer in enumerate(layers):
        heads, f = layer["a_l"].shape
        z = matmul(h, layer["w"], precision).reshape(n, heads, f)
        el = jnp.sum(_mul(z, layer["a_l"][None], precision), axis=-1)
        er = jnp.sum(_mul(z, layer["a_r"][None], precision), axis=-1)
        out = (gat_attention(rowptr, colidx, z, el, er, slope, precision)
               + matmul(h, layer["res"], precision).reshape(n, heads, f))
        if i < len(layers) - 1:
            bn = layer["bn"]
            h = out.reshape(n, heads * f)
            h = ((h - bn["mean"]) / jnp.sqrt(bn["var"] + BN_EPS)
                 * bn["scale"] + bn["shift"])
            h = jax.nn.relu(h)
        else:
            h = jnp.mean(out, axis=1) + params["bias"]
    return h
