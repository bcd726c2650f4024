"""Accel-GCN SpMM — HBM-resident feature matrix variant.

``spmm_accel.py`` keeps the feature tile VMEM-resident, which bounds the
graph at N_pad x 128 x 4B <= 2 MiB per tile (fine for layer-wise GCN
batches, not for web-scale graphs). This variant keeps X in HBM
(``memory_space=ANY``) and gathers the C rows a block needs with explicit
one-row DMAs straight into the gathered slab — the TPU embedding-gather
pattern, driven by the same block-partition metadata. Up to ``DMA_DEPTH``
row copies are in flight at once on one DMA semaphore (every copy moves the
same bytes, so each wait retires one copy). VMEM cost is independent of N,
so this is the fallback regime of ``router.route_spmm`` (N_pad > MAX_WINDOWS
x 4096 at defaults); cost scales with nnz instead.

Each row copy moves ``W`` feature columns, the gather width
``router.hbm_gather_width``: the widest multiple of 128 that divides F_pad
and whose ``[C, W]`` scratch fits the 2 MiB X-tile budget. A copy costs
about the same at 512 B as at 1 KiB (it waits on latency, not bytes), so
the kernel gathers each slot's row once per block at the full padded width
where it fits: W = F_pad up to 2048 at C=256 in f32, and the grid is
``(B, 1)``. Wider features keep ``nf = F_pad / W`` planes and a grid
``(B, nf)`` that gathers each block's rows once per plane. X is laid out
as ``[nf, N_pad, W / 128, 128]``: Mosaic refuses a one-row DMA of an
``[N_pad, W]`` array once W > 128 (its (8, 128) tiling has no one-row
slice), while a row of 128-lane tiles is one whole slice; the gathered
slab is ``[W / 128, C, 128]``, one ``[C, 128]`` MXU operand per lane tile.

Per grid step (C=256, R=64 defaults, f32; W = 256 at F = 256):
  gathered slab          [W/128, C, 128]  C*W*4: 256 KiB at W=256, 2 MiB
                                            at most (one-ROW DMA
                                            granularity: gathered rows are
                                            scattered, so an 8-row slab copy
                                            would move 8x the bytes for one
                                            useful row unless column indices
                                            cluster)
  out slab               [R, W]           R*W*4: 64 KiB at W=256 (x2
                                            pipeline buffers)
  values                 [H, 1, C]          H KiB  (x2 pipeline buffers)
  rowloc                 [C]                1 KiB  (x2 pipeline buffers)
  colidx (SMEM)          [C]                1 KiB  (x2 pipeline buffers)
  weighted one-hot       [R, C]            64 KiB

``colidx`` arrives in SMEM one block per grid step, so each DMA address is
a scalar read (a DMA address cannot come from a VMEM vector element).

With H heads of values (``spmm_accel`` module docstring) a step takes all H
rows of its block's values and reduces lane tile ``t`` of plane ``j`` with
head ``(j * W / 128 + t) // (F_pad / 128 / H)``: each row is still gathered
once per plane, whatever the number of heads.

Batched multi-graph slabs (``spmm_batched`` merge) run unchanged: column
indices arrive pre-shifted into the concatenated feature rows, padded slab
slots carry value 0 with an in-bounds index, and fully-padded bucket blocks
(all values zero) skip their DMA loop entirely and write a zero output
block — so block-count bucketing costs bandwidth only for live blocks.

The kernel writes ``[B, R, F_pad]`` f32, and the shared epilogue
``spmm_accel.scatter_block_rows`` folds it into ``[n_rows, F]`` in the same
jitted module: one gather of every answer row, then a scatter-add of each
block's local row 0 for rows split over blocks; n_rows + B slab rows read,
where a scatter-add of every slot would read B * R.

Validated in interpret mode against the same oracle as the resident-X
kernel, and compiled by Mosaic on a TPU (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_interpret
from .router import hbm_gather_width, pad_features, pad_rows
from .spmm_accel import (
    DEFAULT_F_TILE as LANES, reduce_slab, scatter_block_rows,
    slab_meta_specs, slab_meta_views, value_heads,
)

DMA_DEPTH = 8   # one-row gather copies in flight per grid step


def _kernel(colidx_ref, values_ref, rowloc_ref, x_hbm, out_ref,
            gathered, sem, *, C, R, nf, tph):
    """colidx_ref: int32[1, 1, C] SMEM; values: [H, 1, C] VMEM, the
    block's H heads, ``tph`` lane tiles each; rowloc: [1, 1, C] VMEM;
    x_hbm: [nf, N_pad, L, 128] ANY (the padded features as ``nf = F_pad /
    W`` planes, each row ``L = W / 128`` lane tiles — ANY refs see the
    whole array, so each DMA copies one whole row of plane ``j``); out_ref:
    [1, R, W]; gathered: [L, C, 128] VMEM scratch in X's dtype, lane tile
    ``t`` of slot ``k`` at ``[t, k]``; sem: one DMA semaphore."""
    j = pl.program_id(1)                 # which plane this step gathers

    # Bucket-padding blocks carry all-zero values: skip their C-row DMA loop
    # (and never read the uninitialized gather scratch) — a padded dispatch
    # pays grid-step overhead for dead blocks, not HBM bandwidth.
    live = jnp.any(values_ref[...] != 0.0)

    @pl.when(live)
    def _gather_and_reduce():
        def row_copy(k):
            return pltpu.make_async_copy(
                x_hbm.at[j, colidx_ref[0, 0, k]],
                gathered.at[:, k],
                sem.at[0],
            )

        def issue(k, carry):
            row_copy(k).start()

            @pl.when(k >= DMA_DEPTH)
            def _retire():
                row_copy(k - DMA_DEPTH).wait()

            return carry

        def drain(k, carry):
            row_copy(k).wait()
            return carry

        jax.lax.fori_loop(0, C, issue, 0)
        jax.lax.fori_loop(max(C - DMA_DEPTH, 0), C, drain, 0)
        L = gathered.shape[0]
        for t in range(L):
            # A static head where the plane cannot change it (one plane,
            # or one head): H = 1 then compiles to the single-head kernel,
            # with no traced sublane index into the values.
            head = ((j * L + t) // tph if nf > 1 and tph < nf * L
                    else t // tph)
            out_ref[0, :, t * LANES:(t + 1) * LANES] = reduce_slab(
                values_ref, rowloc_ref, gathered[t].astype(jnp.float32), R,
                head)

    @pl.when(jnp.logical_not(live))
    def _dead_block():
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_rows", "interpret"))
def _spmm_block_slabs_hbm(colidx, values, rowloc, out_row, x, n_rows, *,
                          interpret):
    B, C = colidx.shape
    R = out_row.shape[1]
    N, F = x.shape
    F_pad = pad_features(F, LANES)
    N_pad = pad_rows(N)
    W = hbm_gather_width(F_pad, C, jnp.dtype(x.dtype).itemsize)
    nf, L = F_pad // W, W // LANES
    H, tph = value_heads(values, F_pad // LANES)
    # planes [nf, N_pad, L, 128] (module docstring); at nf == 1 the
    # transpose only moves a unit axis
    x_p = (jnp.zeros((N_pad, F_pad), x.dtype).at[:N, :F].set(x)
           .reshape(N_pad, nf, L, LANES).transpose(1, 0, 2, 3))

    out_slabs = pl.pallas_call(
        functools.partial(_kernel, C=C, R=R, nf=nf, tph=tph),
        grid=(B, nf),
        in_specs=slab_meta_specs(C, lambda b, j: (b, 0, 0), heads=H) + [
            pl.BlockSpec(memory_space=pl.ANY),      # X stays in HBM
        ],
        out_specs=pl.BlockSpec((1, R, W), lambda b, j: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, R, F_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((L, C, LANES), x_p.dtype),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        interpret=interpret,
    )(*slab_meta_views(colidx, values, rowloc), x_p)

    return scatter_block_rows(out_slabs, out_row, n_rows, F)


def spmm_block_slabs_hbm(colidx, values, rowloc, out_row, x, n_rows):
    """HBM-gather SpMM over packed slabs, ``values`` ``[B, C]`` or ``[B, H,
    C]`` (H heads); returns [n_rows, F] float32."""
    return _spmm_block_slabs_hbm(colidx, values, rowloc, out_row, x, n_rows,
                                 interpret=pallas_interpret())
