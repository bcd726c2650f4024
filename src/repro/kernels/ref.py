"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth for every kernel test: simple, obviously-correct
implementations with no tiling, padding, or layout tricks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["csr_spmm_ref", "attention_spmm_ref", "slab_spmm_ref",
           "grouped_matmul_ref"]


def csr_spmm_ref(rowptr: np.ndarray, colidx: np.ndarray, values: np.ndarray,
                 x: jax.Array) -> jax.Array:
    """CSR SpMM oracle: out[r] = sum_k values[k] * x[colidx[k]] for k in row r.

    COO expansion + segment_sum — the canonical jnp formulation.
    """
    n = len(rowptr) - 1
    row_of = np.repeat(np.arange(n), np.diff(rowptr))
    if len(colidx) == 0:
        return jnp.zeros((n, x.shape[1]), dtype=jnp.promote_types(x.dtype, jnp.float32))
    contrib = values[:, None].astype(jnp.float32) * x[colidx].astype(jnp.float32)
    out = jax.ops.segment_sum(contrib, jnp.asarray(row_of), num_segments=n,
                              indices_are_sorted=True)   # CSR row order
    return out


def attention_spmm_ref(rowptr: np.ndarray, colidx: np.ndarray, z: jax.Array,
                       el: jax.Array, er: jax.Array,
                       negative_slope: float = 0.2) -> jax.Array:
    """Graph attention oracle, per head h over row i's nonzeros j:
    ``out[i, h] = sum_j softmax_j(LeakyReLU(el[j, h] + er[i, h])) z[j, h]``.

    z: ``[n_cols, H, F]``; el: ``[n_cols, H]``; er: ``[n_rows, H]``. COO
    expansion with ``segment_max``, ``exp`` and ``segment_sum``, in f32.
    """
    n = len(rowptr) - 1
    row_of = jnp.asarray(np.repeat(np.arange(n), np.diff(rowptr)))
    cols = jnp.asarray(colidx)
    z, el, er = (jnp.asarray(a, jnp.float32) for a in (z, el, er))
    e = el[cols] + er[row_of]                                   # [nnz, H]
    e = jnp.where(e >= 0, e, negative_slope * e)
    m = jax.ops.segment_max(e, row_of, num_segments=n,
                            indices_are_sorted=True)
    p = jnp.exp(e - m[row_of])
    s = jax.ops.segment_sum(p, row_of, num_segments=n,
                            indices_are_sorted=True)
    alpha = p / s[row_of]
    return jax.ops.segment_sum(alpha[:, :, None] * z[cols], row_of,
                               num_segments=n, indices_are_sorted=True)


def slab_spmm_ref(colidx: jax.Array, values: jax.Array, rowloc: jax.Array,
                  out_row: jax.Array, x: jax.Array, n_rows: int) -> jax.Array:
    """Oracle for the slab layout (mirrors the kernel's math step by step).

    colidx/values/rowloc: [B, C]; out_row: [B, R]; x: [N, F].
    """
    B, C = colidx.shape
    R = out_row.shape[1]
    gathered = values[..., None].astype(jnp.float32) * x[colidx].astype(jnp.float32)
    onehot = jax.nn.one_hot(rowloc, R, dtype=jnp.float32)          # [B, C, R]
    slab_out = jnp.einsum("bcr,bcf->brf", onehot, gathered)         # [B, R, F]
    flat = slab_out.reshape(B * R, -1)
    seg = out_row.reshape(B * R)
    out = jax.ops.segment_sum(flat, seg, num_segments=n_rows + 1)
    return out[:n_rows]


def grouped_matmul_ref(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Grouped GEMM oracle: rows of x are grouped contiguously by expert.

    x: [M, K]; w: [E, K, N]; group_sizes: int32[E] summing to M.
    out[m] = x[m] @ w[e(m)] where e(m) is m's group.
    """
    M = x.shape[0]
    e_of_row = jnp.repeat(jnp.arange(w.shape[0]), group_sizes, total_repeat_length=M)
    w_rows = w[e_of_row]  # [M, K, N] — oracle only; memory-naive on purpose
    return jnp.einsum("mk,mkn->mn", x.astype(jnp.float32), w_rows.astype(jnp.float32))
