"""Grouped (expert-blocked) matmul — Accel-GCN block partitioning for MoE.

Token->expert dispatch is a sparse aggregation with power-law-ish "expert
degrees": exactly the workload shape Accel-GCN targets. We reuse the paper's
recipe one-to-one (DESIGN.md §4):

* degree sorting  -> sort tokens by assigned expert (stable);
* block partition -> cut the sorted token rows into fixed ``m_tile`` blocks,
  padding each expert's rows to a block multiple; one int32 metadata word per
  block (its expert id) is the analogue of the paper's 128-bit block record,
  and is *scalar-prefetched* so the weight BlockSpec index_map can read it —
  the TPU equivalent of the paper's metadata-driven warp workload deduction;
* combined warp   -> the expert weight matrix and the output are tiled at 128
  lanes; every grid step runs a dense, fully-aligned MXU matmul.

Every grid step has *identical* FLOPs — the workload-balance property the
paper's Algorithm 2 provides for SpMM.

VMEM per step (defaults, f32): x (128x512)=256 KiB, w (512x128)=256 KiB,
out (128x128)=64 KiB — comfortably within a v5e core's ~16 MiB VMEM, with
room for double-buffered DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_interpret


def _gmm_kernel(expert_ref, x_ref, w_ref, out_ref):
    """x_ref: [m_tile, k_tile]; w_ref: [1, k_tile, n_tile]; out: [m_tile, n_tile]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("m_tile", "k_tile", "n_tile", "interpret"))
def _grouped_matmul(x, w, block_expert, *, m_tile, k_tile, n_tile, interpret):
    M, K = x.shape
    E, K2, N = w.shape
    assert K == K2 and M % m_tile == 0, (x.shape, w.shape, m_tile)
    nb = M // m_tile
    k_tile = min(k_tile, K)
    n_tile = min(n_tile, N)
    assert K % k_tile == 0 and N % n_tile == 0
    nk, nn = K // k_tile, N // n_tile

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nn, nk),
        in_specs=[
            pl.BlockSpec((m_tile, k_tile), lambda b, j, k, e: (b, k)),
            pl.BlockSpec((1, k_tile, n_tile), lambda b, j, k, e: (e[b], k, j)),
        ],
        out_specs=pl.BlockSpec((m_tile, n_tile), lambda b, j, k, e: (b, j)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(block_expert, x, w)


def grouped_matmul(
    x: jax.Array,             # [M, K] rows sorted+padded by expert; M % m_tile == 0
    w: jax.Array,             # [E, K, N]
    block_expert: jax.Array,  # int32[M // m_tile] expert id per row block
    *,
    m_tile: int = 128,
    k_tile: int = 512,
    n_tile: int = 128,
) -> jax.Array:
    """Block-balanced grouped GEMM; returns [M, N] float32.

    The w BlockSpec's index_map reads the scalar-prefetched ``block_expert``
    metadata, so each grid step DMAs exactly one expert's (k_tile x n_tile)
    weight tile — the same "all warps deduce their workload from one block
    record" trick as the paper's int4 metadata.
    """
    return _grouped_matmul(x, w, block_expert, m_tile=m_tile, k_tile=k_tile,
                           n_tile=n_tile, interpret=pallas_interpret())
