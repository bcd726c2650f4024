"""Per-call attention values in slab layout: graph attention's edge scores
and their softmax over each row's nonzeros, on the device.

A graph attention request (GAT, Veličković et al., arXiv:1710.10903) with H
heads brings ``el: [n_cols, H]``, a score per source row (indexed by the
column of a nonzero), and ``er: [n_rows, H]``, a score per output row.
Slot ``k`` of block ``b``, holding column ``j`` of output row ``i``, gets
per head ``h``

    e[b, h, k]     = LeakyReLU(el[j, h] + er[i, h])  (negative slope per head)
    alpha[b, h, k] = exp(e[b, h, k] - max_i) / sum over row i's slots of
                     exp(e - max_i),  max_i the largest e of row i

which is what the slab kernels then reduce with, head by head
(``spmm_accel.reduce_slab``). The plan supplies only structure: the slot's
column (``colidx``), its row (``out_row[b, rowloc[b, k]]``, a position in
the plan's degree-sorted order, so ``er`` arrives in that order, see
:func:`to_plan_order`) and which slots are padding (slot ``k`` of block
``b`` is live iff ``k < nnz[b]``). Padding slots get exactly 0.

On the TPU a gather's time follows its count of indices (several ns
each), so ``el`` is gathered per slot for all H heads at once (columns of
``el.T``, ``[H, B, C]``) and ``er`` only per block row (``[H, B, R]``),
then spread to the slots by a compare-and-sum over the block's R rows,
which fuses into one pass.

The softmax runs in two steps. Within a block, a row's slots are contiguous
(``core/partition.py`` packs rows in order), so each row's maximum and sum
come from a segmented scan along the slots, ``log2(C)`` shifted steps, and
a scan back that copies each row's total to all of its slots; padding
slots form a segment of their own. A row longer than a block's capacity C
is split over consecutive blocks, one row per block at local row 0; so the
maxima and sums of every block's local row 0 are combined over the blocks
that share that row, by a segment reduction keyed by ``out_row[:, 0]``. A
row that no block splits is its own segment there, and other local rows
never span blocks.

``_edge_softmax_slabs`` also fills the heads that are not attention: per
block and head, ``kind`` says whether the head takes the plan's own values
(``PLAIN``, a plain aggregation fused beside attention requests) or zeros
(``NONE``, a head this graph does not use in a dispatch whose other graphs
do).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["NONE", "PLAIN", "ATTENTION", "edge_softmax_slabs",
           "to_plan_order"]

NONE, PLAIN, ATTENTION = 0, 1, 2   # per-(block, head) source of the values
TAKE_STEP = 1 << 19   # indices per gather step: 256 MiB of lane-padded rows


@jax.jit
def _to_plan_order(a, inv_perm):
    return jnp.zeros_like(a).at[inv_perm].set(a)


def to_plan_order(a: jax.Array, inv_perm: jax.Array) -> jax.Array:
    """Rows of ``a`` (original row order) moved to the plan's degree-sorted
    positions: ``out[inv_perm[i]] = a[i]``."""
    return _to_plan_order(a, inv_perm)


def _shift(a, s: int, fill, reverse: bool):
    """``a`` moved ``s`` slots along its last axis (towards higher slots, or
    lower ones if ``reverse``), the vacated slots ``fill``."""
    pad = jnp.full(a.shape[:-1] + (s,), fill, a.dtype)
    if reverse:
        return jnp.concatenate([a[..., s:], pad], axis=-1)
    return jnp.concatenate([pad, a[..., :-s]], axis=-1)


def _segmented(a, bound, op, reverse: bool = False):
    """Inclusive scan of ``op`` along the last axis within segments, each
    starting where ``bound`` is set (ending there, scanning in
    ``reverse``): Hillis-Steele, one shifted combine per power of two."""
    step = 1
    while step < a.shape[-1]:
        a = jnp.where(bound, a, op(_shift(a, step, 0, reverse), a))
        bound = bound | _shift(bound, step, True, reverse)
        step *= 2
    return a


def _row_totals(a, start, end, op):
    """Each slot's row total of ``op`` over ``[H, B, C]``: a scan to each
    row's last slot, then a scan back that copies it to the row's slots."""
    total = _segmented(a, start, op)
    return _segmented(total, end, lambda later, cur: later, reverse=True)


def _take_heads(table, idx):
    """``table[idx]`` of a ``[n, H]`` table as ``[H, *idx.shape]``, all H
    heads of an index in one gather step. The TPU lays a step's ``[k, H]``
    result out with H padded to 128 lanes, so the gather runs over rows of
    ``idx`` in steps of ``TAKE_STEP`` indices (a loop would let that
    layout reach its stacked output)."""
    rows = max(1, TAKE_STEP // idx.shape[1])
    return jnp.concatenate(
        [jnp.take(table.T, idx[b:b + rows], axis=1)
         for b in range(0, idx.shape[0], rows)], axis=1)


@jax.jit
def _edge_softmax_slabs(colidx, values, rowloc, out_row, nnz, kind, slope,
                        el, er):
    # heads lead: a minor axis of H pads to 128 lanes on the TPU
    B, C = colidx.shape
    R = out_row.shape[1]
    n_seg = er.shape[0]
    live = (jax.lax.broadcasted_iota(jnp.int32, (B, C), 1)
            < nnz[:, None])                                 # [B, C]
    el_slot = _take_heads(el, colidx)                       # [H, B, C]
    # er by block row, then to each slot by its local row: a gather's cost
    # follows its count of indices, and R < C
    er_row = _take_heads(er, out_row)                       # [H, B, R]
    onehot = rowloc[:, None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (1, R, 1), 1)                            # [B, R, C]
    er_slot = jnp.sum(jnp.where(onehot, er_row[..., None], 0.0), axis=2)
    s = el_slot + er_slot
    e = jnp.where(s >= 0, s, slope.T[:, :, None] * s)
    e = jnp.where(live, e, -jnp.inf)
    seg = jnp.where(live, rowloc, R)            # padding: a segment of its own
    start = seg != _shift(seg, 1, -1, False)
    end = seg != _shift(seg, 1, -1, True)
    row0 = live & (rowloc == 0)
    first = out_row[:, 0]                                   # [B]

    def across_blocks(a, segment_op):   # combine local row 0 of split rows
        whole = segment_op(a[:, :, 0].T, first, num_segments=n_seg)
        return jnp.where(row0, whole[first].T[:, :, None], a)

    m = across_blocks(_row_totals(e, start, end, jnp.maximum),
                      jax.ops.segment_max)
    p = jnp.where(live, jnp.exp(e - m), 0.0)
    total = across_blocks(_row_totals(p, start, end, jnp.add),
                          jax.ops.segment_sum)
    alpha = jnp.where(live, p / total, 0.0)
    k = kind.T[:, :, None]
    out = jnp.where(k == ATTENTION, alpha,
                    jnp.where(k == PLAIN, values[None], 0.0))
    return jnp.moveaxis(out, 0, 1)                          # [B, H, C]


def edge_softmax_slabs(colidx, values, rowloc, out_row, nnz, kind, slope,
                       el, er) -> jax.Array:
    """Values ``f32[B, H, C]`` of one dispatch's H heads (module docstring).

    colidx, values, rowloc: ``[B, C]`` and out_row ``[B, R]``, the merged
    slabs (``values`` the plan's own); nnz: ``int32[B]`` live slots per
    block; kind: ``int32[B, H]`` (``NONE``, ``PLAIN`` or ``ATTENTION``);
    slope: ``f32[B, H]`` LeakyReLU negative slope; el: ``f32[n_cols, H]``
    by feature row; er: ``f32[n_out + 1, H]`` by output row in the merged
    degree-sorted order, its last row that of the drop sentinel.
    """
    return _edge_softmax_slabs(colidx, values, rowloc, out_row, nnz, kind,
                               slope, el, er)
