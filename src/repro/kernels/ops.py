"""Jit'd wrappers around the Pallas kernels + portable jnp twins.

Every kernel has three callables:
  * ``*_pallas``  — the Pallas kernel (interpreted on CPU, compiled on TPU;
                    see ``platform.pallas_interpret``)
  * ``*_blocked`` — a pure-jnp twin with the *same* slab layout and math
                    (the portable production path; XLA fuses it well)
  * oracle        — in ref.py (layout-free ground truth)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .router import route_spmm
from .spmm_accel import (
    scatter_block_rows, spmm_block_slabs, spmm_block_slabs_windowed,
)
from .spmm_hbm import spmm_block_slabs_hbm
from .grouped_matmul import grouped_matmul

__all__ = ["spmm_pallas", "spmm_pallas_windowed", "spmm_pallas_hbm",
           "spmm_auto", "spmm_blocked", "spmm_batched",
           "grouped_matmul_pallas", "grouped_matmul_blocked"]


def spmm_batched(slab_list, x_list, n_rows_list, *, backend="pallas",
                 pad_blocks_to=None, return_decision=False):
    """Fused multi-graph SpMM (one pallas_call for the whole batch)."""
    from .spmm_batched import spmm_batched as _batched
    return _batched(slab_list, x_list, n_rows_list, backend=backend,
                    pad_blocks_to=pad_blocks_to,
                    return_decision=return_decision)


def spmm_pallas(slabs, x, n_rows):
    """Resident-X kernel; raises VmemBudgetError past N_pad <= 4096 (f32)."""
    return spmm_block_slabs(
        slabs["colidx"], slabs["values"], slabs["rowloc"], slabs["out_row"],
        x, n_rows,
    )


def spmm_pallas_windowed(slabs, x, n_rows, *, window_rows=None):
    """Row-window streaming variant: X visits VMEM one window at a time."""
    return spmm_block_slabs_windowed(
        slabs["colidx"], slabs["values"], slabs["rowloc"], slabs["out_row"],
        x, n_rows, window_rows=window_rows,
    )


def spmm_pallas_hbm(slabs, x, n_rows):
    """HBM-resident X variant (pipelined one-row DMA gather) for graphs
    whose feature tile exceeds VMEM."""
    return spmm_block_slabs_hbm(
        slabs["colidx"], slabs["values"], slabs["rowloc"], slabs["out_row"],
        x, n_rows,
    )


def spmm_auto(slabs, x, n_rows, *, return_decision=False):
    """VMEM-routed single-graph dispatch: resident / windowed / hbm chosen
    from the feature-operand shape (see ``router.route_spmm``)."""
    decision = route_spmm(
        int(x.shape[0]), int(x.shape[1]),
        int(slabs["C"]), int(slabs["R"]),
        itemsize=jnp.dtype(x.dtype).itemsize)
    fn = {"resident": spmm_pallas, "windowed": spmm_pallas_windowed,
          "hbm": spmm_pallas_hbm}[decision.backend]
    out = fn(slabs, x, n_rows)
    return (out, decision) if return_decision else out


@functools.partial(jax.jit, static_argnames=("n_rows", "block_chunk"))
def spmm_blocked(colidx, values, rowloc, out_row, x, n_rows, block_chunk: int = 1024):
    """jnp twin of the Pallas kernel: identical slab math, chunked over blocks
    to bound the gathered-intermediate footprint (the VMEM analogue).
    ``values`` ``[B, H, C]`` weighs the features as H heads of ``F / H``
    columns, one set of slot values per head; ``[B, C]`` is one head."""
    B, C = colidx.shape
    values = values.reshape(B, -1, C)
    H = values.shape[1]
    R = out_row.shape[1]
    F = x.shape[1]
    bc = min(block_chunk, B) if B else 1
    Bp = ((B + bc - 1) // bc) * bc if B else bc
    pad = Bp - B

    def padded(a, fill):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), constant_values=fill)

    ci = padded(colidx, 0).reshape(-1, bc, C)
    va = padded(values, 0).reshape(-1, bc, H, C)
    rl = padded(rowloc, R - 1).reshape(-1, bc, C)

    def chunk_fn(args):
        ci_c, va_c, rl_c = args
        # head h weighs its F / H columns
        xg = x[ci_c].astype(jnp.float32).reshape(bc, C, H, F // H)
        gathered = (jnp.moveaxis(va_c, 1, 2)[..., None].astype(jnp.float32)
                    * xg).reshape(bc, C, F)
        onehot = jax.nn.one_hot(rl_c, R, dtype=jnp.float32)
        # HIGHEST: the TPU's default f32 matmul rounds operands to bf16
        return jnp.einsum("bcr,bcf->brf", onehot, gathered,
                          precision=jax.lax.Precision.HIGHEST)

    slab_out = jax.lax.map(chunk_fn, (ci, va, rl))          # [nc, bc, R, F]
    return scatter_block_rows(slab_out.reshape(Bp, R, F),
                              padded(out_row, n_rows), n_rows, F)


def grouped_matmul_pallas(x, w, block_expert, **tiles):
    return grouped_matmul(x, w, block_expert, **tiles)


@functools.partial(jax.jit, static_argnames=("m_tile",))
def grouped_matmul_blocked(x, w, block_expert, m_tile: int = 128):
    """jnp twin: per-block dynamic weight pick + dense matmul, scanned."""
    M, K = x.shape
    nb = M // m_tile
    xb = x.reshape(nb, m_tile, K)

    def step(_, args):
        xt, e = args
        return None, jnp.dot(xt.astype(jnp.float32), w[e].astype(jnp.float32),
                             preferred_element_type=jnp.float32)

    _, out = jax.lax.scan(step, None, (xb, block_expert))
    return out.reshape(M, -1)
