"""Accel-GCN SpMM as a Pallas TPU kernel.

TPU mapping of the paper's design (the TPU pattern mode of
``core/partition.py``):

* one grid step == one *block* of the block-level partition: a fixed-capacity
  slab of ``C = deg_bound`` non-zeros covering up to ``R`` contiguous
  (degree-sorted) output rows;
* the dense feature dimension is tiled at 128 lanes and iterated by a second
  grid axis — the *combined warp*: every HBM<->VMEM transfer of a dense row is
  a full-lane contiguous vector;
* the intra-block segment reduction (the paper's shared-memory
  ``atomicAdd_block``) becomes a weighted one-hot MXU matmul
  ``[R, C] @ [C, F_tile]`` entirely in VMEM — no atomics exist or are needed;
* the packed ``[B, R, F_pad]`` block outputs fold into ``[n_rows, F]`` in
  one shared epilogue, :func:`scatter_block_rows`, which reads each kept
  slab row once: every answer row by one gather from a block that covers
  it, then a scatter-add of each block's local row 0 that adds the other
  partials of a row split over blocks (degree > C).

Slab metadata enters each grid step as ``[1, 1, C]`` blocks of the
``[B, 1, C]`` view of the ``[B, C]`` slabs (a ``(1, C)`` block of a
``[B, C]`` array breaks the TPU's (8, 128) block-tiling rule).

The values may carry H heads, ``[B, H, C]``: one set of slot values per
head, computed per call (``edge_softmax.py``), over features laid out as H
heads of ``F_pad / H`` columns each, a whole number of 128-lane tiles. Lane
tile ``t`` is reduced with head ``t // (F_pad / 128 / H)``. The values'
view is then ``[B * H, 1, C]``: the resident and windowed kernels take
their tile's head as a ``[1, 1, C]`` block, the HBM kernel all H of its
block as ``[H, 1, C]``. With H = 1 the values are ``[B, C]``, every
tile's head is 0, and every kernel computes what it does without heads.

``colidx`` goes to SMEM, one block per step, and is read as scalars: the row gather is
a loop of scalar-addressed row reads into a ``[C, F_tile]`` VMEM scratch (a
vector-indexed gather ``x_ref[cols, :]`` does not lower on the TPU). The
whole ``colidx`` array is never scalar-prefetched: at full size it is far
larger than SMEM.

VMEM budget per grid step (f32, defaults C=256, R=64, F_tile=128; the
HBM kernel's gather width W is ``router.hbm_gather_width``: F_pad up to
2048 at C=256, so W=256 at F=256; the routing arithmetic lives in
``router.py``):

  term              resident          windowed          hbm (spmm_hbm)
  ----------------  ----------------  ----------------  -----------------
  X feature tile    [N_pad, F_tile]   [4096, F_tile]    none: rows DMA from
                    N_pad<=4096: 2MiB  2 MiB x 2 bufs   HBM into the slab
  gathered slab     [C, F_tile]       [C, F_tile]       [C, W]
                    128 KiB           128 KiB           C*W*4, <= 2 MiB
  out slab          [R, F_tile]       [R, F_tile]       [R, W]
                    32 KiB (x2 bufs)  32 KiB (x2 bufs)  R*W*4 (x2 bufs)
  values/rowloc [C] 3 KiB  (x2 bufs)  3 KiB  (x2 bufs)  3 KiB  (x2 bufs)
  (+colidx in SMEM)
  weighted one-hot  64 KiB            64 KiB            64 KiB
  [R, C]

* ``spmm_block_slabs`` (resident): the whole X tile sits in VMEM. Guarded —
  N_pad over the 2 MiB tile budget raises ``VmemBudgetError`` at trace time
  (on hardware it would be a Mosaic compile failure, not a slowdown).
* ``spmm_block_slabs_windowed``: X streams through VMEM in row windows of
  ``window_rows`` (default 4096); a third grid axis sweeps the windows and
  accumulates into the revisited output block (TPU grids are sequential, so
  revisit accumulation is legal). Middle regime: N_pad <= 4 windows.
* beyond that, ``spmm_hbm.spmm_block_slabs_hbm`` gathers rows straight from
  HBM. ``router.route_spmm`` picks between the three automatically.

Every entry point runs compiled on a TPU and interpreted on the CPU
(:func:`repro.kernels.platform.pallas_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import pallas_interpret
from .router import (
    assert_resident_fits,
    pad_features,
    pad_rows,
    resident_window_rows,
)


DEFAULT_F_TILE = 128  # lane width — the "combined warp" quantum on TPU


def scatter_block_rows(out_slabs: jax.Array, out_row: jax.Array,
                       n_rows: int, n_features: int) -> jax.Array:
    """Shared epilogue of every slab kernel: packed [B, R, F_pad] block
    rows -> global [n_rows, n_features], reading each slab row it keeps
    once (n_rows + B rows, where a scatter-add of every slot reads B * R).

    It relies on the layout ``partition.pack_slabs`` gives and the batched
    merge and plan repair keep, in any block order:

    * block b's live local rows are a prefix ``r < n_b``, with
      ``out_row[b, r] = out_row[b, 0] + r``; the rest hold the drop
      sentinel ``n_rows``;
    * blocks of more than one row cover disjoint row ranges;
    * a row split over several blocks (degree > C) is local row 0 of each.

    Every row is one gather from a block that covers it: each block's index
    is written at its first row and filled forward (a running maximum over
    the rows), and so is its end. A split row gathers its first block's
    partial, and its later blocks add theirs by a scatter of local row 0,
    B rows, in block order as a scatter-add of every slot would. Rows that
    no block covers (no non-zeros) come out zero.
    """
    B, R, F_pad = out_slabs.shape
    if not B:
        return jnp.zeros((n_rows, n_features), out_slabs.dtype)
    first = out_row[:, 0]
    n_b = jnp.sum(out_row != n_rows, axis=1, dtype=jnp.int32)
    blk = jnp.arange(B, dtype=jnp.int32)
    owner = jnp.full((n_rows + 1,), B, jnp.int32).at[first].min(blk)
    end = jnp.zeros((n_rows + 1,), jnp.int32).at[first].max(first + n_b)
    pos = jnp.arange(n_rows, dtype=jnp.int32)
    start = jax.lax.cummax(jnp.where(owner[:n_rows] < B, pos, -1))
    # ranges are disjoint, so the running maximum of the ends is the end of
    # the block that starts last at or before each row
    body = pos < jax.lax.cummax(end[:n_rows])
    src = jnp.where(body, owner[jnp.maximum(start, 0)] * R + pos - start, 0)
    # whole padded rows: the TPU lowers a gather of fewer lanes than the
    # row holds (F = 40 of 128) to a loop of one-row copies
    rows = out_slabs.reshape(B * R, F_pad).at[src].get(
        mode="promise_in_bounds")
    out = jnp.where(body[:, None], rows, 0.0)
    rest = jnp.where(owner[first] == blk, n_rows, first)  # n_rows: dropped
    out = out.at[rest].add(out_slabs[:, 0], mode="drop")
    return out[:, :n_features]


def slab_meta_specs(C: int, index_map, values_map=None, heads: int = 1):
    """BlockSpecs of the ``[B, 1, C]`` colidx / rowloc views and the
    ``[B * H, 1, C]`` values view: one block per grid step; colidx in SMEM
    (scalar row addresses), values and rowloc in VMEM (vector operands of
    the weighted one-hot). ``values_map`` (default ``index_map``) places the
    ``[heads, 1, C]`` values block in units of ``heads`` rows."""
    return [
        pl.BlockSpec((1, 1, C), index_map, memory_space=pltpu.SMEM),
        pl.BlockSpec((heads, 1, C), values_map or index_map),
        pl.BlockSpec((1, 1, C), index_map),
    ]


def slab_meta_views(colidx, values, rowloc):
    """``[B, C]`` slab metadata and ``[B, C]`` or ``[B, H, C]`` values ->
    the ``[B, 1, C]`` and ``[B * H, 1, C]`` views the kernels take."""
    B, C = colidx.shape
    return (colidx.reshape(B, 1, C), values.reshape(-1, 1, C),
            rowloc.reshape(B, 1, C))


def value_heads(values, f_lanes: int) -> tuple[int, int]:
    """``(H, tiles_per_head)`` of ``values`` over ``f_lanes`` 128-lane tiles
    of features: every head owns a whole number of tiles."""
    H = values.shape[1] if values.ndim == 3 else 1
    if f_lanes % H:
        raise ValueError(f"{f_lanes} feature tiles do not split into "
                         f"{H} heads of whole tiles")
    return H, f_lanes // H


def reduce_slab(values_ref, rowloc_ref, gathered: jax.Array,
                R: int, head=0) -> jax.Array:
    """Intra-block segment reduction ``[R, C] @ [C, F_tile]`` on the MXU.

    The one-hot row map carries each slot's value for ``head`` (row
    ``head`` of the ``[H, 1, C]`` values block), so padding slots (value 0)
    contribute nothing. HIGHEST precision keeps the f32 products exact on
    the MXU's multi-pass path.
    """
    vals = values_ref[head].astype(jnp.float32)           # [1, C]
    rloc = rowloc_ref[0]                                  # [1, C]
    C = vals.shape[1]
    weights = jnp.where(
        rloc == jax.lax.broadcasted_iota(jnp.int32, (R, C), 0),
        vals, 0.0)                                        # [R, C]
    return jax.lax.dot_general(
        weights, gathered, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _spmm_kernel(colidx_ref, values_ref, rowloc_ref, x_ref, out_ref,
                 gathered, *, C, R):
    """One block x one feature tile.

    colidx_ref: int32[1, 1, C] SMEM; values_ref: f32[1, 1, C] (the tile's
    head); rowloc_ref: int32[1, 1, C]; x_ref: [N_pad, F_tile] feature tile (VMEM
    resident); out_ref: [1, R, F_tile]; gathered: f32[C, F_tile] scratch.
    """
    # Gather C dense rows from the feature tile: one scalar-addressed,
    # full-lane row read per slot (padding slots read a valid row and are
    # zeroed by their value in the reduction).
    def gather(k, carry):
        col = colidx_ref[0, 0, k]
        gathered[pl.ds(k, 1), :] = x_ref[pl.ds(col, 1), :].astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, C, gather, 0)
    out_ref[0] = reduce_slab(values_ref, rowloc_ref, gathered[...], R)


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "interpret", "f_tile", "grid_order"),
)
def _spmm_block_slabs(colidx, values, rowloc, out_row, x, n_rows, *,
                      f_tile, grid_order, interpret):
    if grid_order not in ("block_major", "ft_major"):
        raise ValueError(
            f"grid_order must be block_major|ft_major, got {grid_order!r}")
    B, C = colidx.shape
    R = out_row.shape[1]
    N, F = x.shape
    assert_resident_fits(N, F, C, R, f_tile=f_tile,
                         itemsize=jnp.dtype(x.dtype).itemsize)

    # Combined-warp alignment: pad F to the lane width (paper's pad-to-32,
    # scaled to TPU's 128 lanes), pad N to sublane multiple.
    F_pad = pad_features(F, f_tile)
    N_pad = pad_rows(N)
    x_p = jnp.zeros((N_pad, F_pad), x.dtype).at[:N, :F].set(x)
    nf = F_pad // f_tile
    H, tph = value_heads(values, nf)

    if grid_order == "block_major":
        grid = (B, nf)
        block_ix = lambda b, j: (b, 0, 0)       # noqa: E731
        head_ix = lambda b, j: (b * H + j // tph, 0, 0)  # noqa: E731
        x_ix = lambda b, j: (0, j)              # noqa: E731
        out_ix = lambda b, j: (b, 0, j)         # noqa: E731
    else:  # ft_major: (feature-tile, block) — block axis innermost
        grid = (nf, B)
        block_ix = lambda j, b: (b, 0, 0)       # noqa: E731
        head_ix = lambda j, b: (b * H + j // tph, 0, 0)  # noqa: E731
        x_ix = lambda j, b: (0, j)              # noqa: E731
        out_ix = lambda j, b: (b, 0, j)         # noqa: E731
    out_slabs = pl.pallas_call(
        functools.partial(_spmm_kernel, C=C, R=R),
        grid=grid,
        in_specs=slab_meta_specs(C, block_ix, head_ix) + [
            pl.BlockSpec((N_pad, f_tile), x_ix),
        ],
        out_specs=pl.BlockSpec((1, R, f_tile), out_ix),
        out_shape=jax.ShapeDtypeStruct((B, R, F_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, f_tile), jnp.float32)],
        interpret=interpret,
    )(*slab_meta_views(colidx, values, rowloc), x_p)

    return scatter_block_rows(out_slabs, out_row, n_rows, F)


def spmm_block_slabs(
    colidx: jax.Array,   # int32[B, C]
    values: jax.Array,   # f32[B, C], or f32[B, H, C] for H heads
    rowloc: jax.Array,   # int32[B, C]
    out_row: jax.Array,  # int32[B, R]
    x: jax.Array,        # [N, F]
    n_rows: int,
    *,
    f_tile: int = DEFAULT_F_TILE,
    grid_order: str = "block_major",
) -> jax.Array:
    """Run the Accel-GCN SpMM kernel over packed slabs; returns [n_rows, F].

    ``grid_order`` picks the iteration order of the 2D grid (the ROADMAP
    grid-order experiment; every (block, feature-tile) pair runs exactly
    once either way, so outputs are identical):

    * ``"block_major"`` (default): grid ``(B, nf)`` — the feature-tile
      axis is innermost, so one block's slab metadata stays put while its
      feature tiles sweep (one slab fetch per block, nf X-tile switches).
    * ``"ft_major"``: grid ``(nf, B)`` — the block axis is innermost, so
      ONE X feature tile stays resident across the whole block sweep; the
      per-step revisit cost moves to the (much smaller) slab metadata.
      This is the order that should win on real hardware once the X tile
      dominates the per-step DMA traffic.

    Raises :class:`repro.kernels.router.VmemBudgetError` when the resident
    X tile would not fit the VMEM budget (N_pad > 4096 at f32 defaults);
    oversized graphs belong to ``spmm_block_slabs_windowed`` or the HBM
    gather kernel — ``backend="auto"`` picks for you.
    """
    return _spmm_block_slabs(colidx, values, rowloc, out_row, x, n_rows,
                             f_tile=f_tile, grid_order=grid_order,
                             interpret=pallas_interpret())


def _spmm_kernel_windowed(colidx_ref, values_ref, rowloc_ref, x_ref, out_ref,
                          gathered, *, C, R, window):
    """One block x one feature tile x one row window of X.

    x_ref: [window, F_tile] — the w-th row window of the padded features.
    Slots whose column falls outside the window contribute zero this sweep
    and are picked up by the sweep that owns them; the revisited output
    block accumulates across the (sequential) window axis.
    """
    w = pl.program_id(2)
    base = w * window

    def gather(k, carry):
        local = colidx_ref[0, 0, k] - base
        inside = (local >= 0) & (local < window)

        @pl.when(inside)
        def _row():
            gathered[pl.ds(k, 1), :] = (
                x_ref[pl.ds(local, 1), :].astype(jnp.float32))

        @pl.when(jnp.logical_not(inside))
        def _zero():
            gathered[pl.ds(k, 1), :] = jnp.zeros(
                (1, gathered.shape[1]), jnp.float32)

        return carry

    jax.lax.fori_loop(0, C, gather, 0)
    contrib = reduce_slab(values_ref, rowloc_ref, gathered[...], R)

    @pl.when(w == 0)
    def _init():
        out_ref[0] = contrib

    @pl.when(w > 0)
    def _accumulate():
        out_ref[0] += contrib


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "interpret", "f_tile", "window_rows"),
)
def _spmm_block_slabs_windowed(colidx, values, rowloc, out_row, x, n_rows, *,
                               f_tile, window_rows, interpret):
    B, C = colidx.shape
    R = out_row.shape[1]
    N, F = x.shape
    window = window_rows or resident_window_rows(
        f_tile, jnp.dtype(x.dtype).itemsize)

    F_pad = pad_features(F, f_tile)
    num_windows = max(1, (N + window - 1) // window)
    N_pad = num_windows * window
    x_p = jnp.zeros((N_pad, F_pad), x.dtype).at[:N, :F].set(x)
    nf = F_pad // f_tile
    H, tph = value_heads(values, nf)

    grid = (B, nf, num_windows)  # window axis innermost: consecutive
    out_slabs = pl.pallas_call(  # revisits of one output block accumulate
        functools.partial(_spmm_kernel_windowed, C=C, R=R, window=window),
        grid=grid,
        in_specs=slab_meta_specs(C, lambda b, j, w: (b, 0, 0),
                                 lambda b, j, w: (b * H + j // tph, 0, 0)) + [
            pl.BlockSpec((window, f_tile), lambda b, j, w: (w, j)),
        ],
        out_specs=pl.BlockSpec((1, R, f_tile), lambda b, j, w: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, R, F_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, f_tile), jnp.float32)],
        interpret=interpret,
    )(*slab_meta_views(colidx, values, rowloc), x_p)

    return scatter_block_rows(out_slabs, out_row, n_rows, F)


def spmm_block_slabs_windowed(
    colidx: jax.Array,   # int32[B, C]
    values: jax.Array,   # f32[B, C], or f32[B, H, C] for H heads
    rowloc: jax.Array,   # int32[B, C]
    out_row: jax.Array,  # int32[B, R]
    x: jax.Array,        # [N, F]
    n_rows: int,
    *,
    f_tile: int = DEFAULT_F_TILE,
    window_rows: int | None = None,
) -> jax.Array:
    """Row-window streaming variant: X visits VMEM one ``window_rows`` tile
    at a time (grid axis 2), so any N fits in the resident budget at the
    price of one full (B, nf) grid sweep per window. Returns [n_rows, F].
    """
    return _spmm_block_slabs_windowed(
        colidx, values, rowloc, out_row, x, n_rows, f_tile=f_tile,
        window_rows=window_rows, interpret=pallas_interpret())
