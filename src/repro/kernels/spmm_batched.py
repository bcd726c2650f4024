"""Batched multi-graph SpMM: many graphs through ONE ``pallas_call``.

Serving traffic arrives as independent per-graph requests, but each graph's
block partition is just a ``[B_g, C_g]`` slab stack — a shape the kernel grid
already iterates block-by-block. So a batch of graphs fuses by construction:

1. pad every graph's slabs to the batch-wide ``(C, R)`` capacity;
2. shift each graph's ``colidx`` by its feature-row offset and its ``out_row``
   by its output-row offset (the per-graph drop sentinel ``n_rows_g`` is
   remapped to the single batch-wide sentinel ``N_out``), then concatenate
   along the block axis;
3. route the merged ``[B_total, C]`` slabs + row-concatenated features to a
   single-graph kernel — ONE compilation, one dispatch, one epilogue.
   The concatenated feature matrix is where a batch of
   individually-fine graphs silently overflows the resident kernel's VMEM
   budget (N_pad multiplies by batch size!), so ``backend="auto"`` asks
   ``router.route_spmm`` to pick resident / windowed / HBM-gather from the
   merged shape, and ``backend="pallas"`` (forced resident) raises
   ``VmemBudgetError`` instead of silently compiling an oversized tile;
4. slice each graph's rows back out of the batched output.

Padding slab slots carry value 0 and padding block rows map to the
sentinel row, so fused outputs are bit-identical in structure to per-graph
runs (fp32 reduction order within a block is unchanged). The merge keeps
the slab layout the kernels' epilogue relies on
(``spmm_accel.scatter_block_rows``): each graph's blocks cover consecutive
output rows, shifted by one offset, and padding is all sentinel.

``pad_blocks_to`` rounds the merged block count up to a bucket so repeated
batches with different graph mixes reuse one compiled kernel.

A dispatch may carry H value heads per graph (:class:`HeadValues`): each
graph's features are then H heads of ``F / H`` columns, and each head's
slot values are computed per call by ``edge_softmax.py`` from the merged
structure, after the upload, as the ``scores`` phase. The per-call inputs
merge like the structure: each graph's per-block live slot counts
(``nnz``), head kinds and negative slopes are concatenated and padded with
the blocks, ``el`` is concatenated along the feature rows and ``er`` along
the output rows.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.spans import LAUNCH, MERGE, PREPARE, SCORES, UPLOAD, span
from .edge_softmax import NONE, edge_softmax_slabs
from .router import RoutingDecision, route_spmm
from .spmm_accel import spmm_block_slabs, spmm_block_slabs_windowed
from .spmm_hbm import spmm_block_slabs_hbm

__all__ = ["batch_graph_slabs", "spmm_batched", "bucket_blocks",
           "HeadValues"]


@dataclasses.dataclass(frozen=True)
class HeadValues:
    """Where one graph's H value heads come from in a dispatch.

    ``kind[h]`` is ``edge_softmax.ATTENTION`` (computed per call from
    ``el``, ``er`` and ``slope[h]``), ``PLAIN`` (the plan's own values) or
    ``NONE`` (zeros). ``el`` is ``[n_cols, H]`` and ``er`` ``[n_rows, H]``
    in the plan's degree-sorted row order (``edge_softmax.to_plan_order``),
    zero in the heads that are not attention.
    """

    kind: np.ndarray      # int32[H]
    slope: np.ndarray     # float32[H]
    el: jax.Array
    er: jax.Array


def bucket_blocks(b_total: int, min_bucket: int = 8) -> int:
    """Next power-of-two block bucket (>= min_bucket) for jit-cache reuse.

    Power-of-two tiers bound padding waste below 2x the live block count
    (for ``b_total >= min_bucket``); the old fixed 256 floor padded a
    3-block batch to 256 blocks — 85x dead grid steps. Raise ``min_bucket``
    only to trade those dead steps for fewer compiled grid shapes.
    """
    bucket = min_bucket
    while bucket < b_total:
        bucket *= 2
    return bucket


def batch_graph_slabs(
    slab_list: Sequence[Dict],
    n_rows_list: Sequence[int],
    n_cols_list: Sequence[int],
    pad_blocks_to: Optional[int] = None,
) -> Tuple[Dict, np.ndarray, np.ndarray, int]:
    """Merge per-graph slab dicts into one batch-wide slab dict.

    Returns ``(merged, out_offsets, col_offsets, n_out_total)`` where
    ``merged`` has the same keys as a single-graph slab dict (colidx, values,
    rowloc, out_row, R, C, and ``nnz``, live slots per block, where every
    graph's dict has it) and graph ``i``'s output rows live at
    ``[out_offsets[i], out_offsets[i] + n_rows_list[i])`` of the batched
    result. Host-side numpy; cost is O(sum B_g * C) copies, far below a
    partition rebuild.
    """
    G = len(slab_list)
    assert G == len(n_rows_list) == len(n_cols_list) and G > 0
    C = max(int(s["C"]) for s in slab_list)
    R = max(int(s["R"]) for s in slab_list)
    out_offsets = np.concatenate(([0], np.cumsum(n_rows_list)))
    col_offsets = np.concatenate(([0], np.cumsum(n_cols_list)))
    n_out = int(out_offsets[-1])

    cols, vals, rlocs, orows = [], [], [], []
    for i, s in enumerate(slab_list):
        ci = np.asarray(s["colidx"], dtype=np.int32)
        va = np.asarray(s["values"], dtype=np.float32)
        rl = np.asarray(s["rowloc"], dtype=np.int32)
        orw = np.asarray(s["out_row"], dtype=np.int32)
        Bg, Cg = ci.shape
        Rg = orw.shape[1]
        # out_row: per-graph sentinel n_rows_g -> batch sentinel n_out, live
        # rows shift by the graph's output offset.
        orw = np.where(orw == n_rows_list[i],
                       n_out, orw + out_offsets[i]).astype(np.int32)
        # colidx shifts into the concatenated feature rows; padding slots
        # (value 0) keep a valid index so the gather stays in bounds.
        ci = ci + np.int32(col_offsets[i])
        if Cg < C:
            ci = np.pad(ci, ((0, 0), (0, C - Cg)),
                        constant_values=int(col_offsets[i]))
            va = np.pad(va, ((0, 0), (0, C - Cg)))
            rl = np.pad(rl, ((0, 0), (0, C - Cg)), constant_values=R - 1)
        if Rg < R:
            orw = np.pad(orw, ((0, 0), (0, R - Rg)), constant_values=n_out)
        cols.append(ci)
        vals.append(va)
        rlocs.append(rl)
        orows.append(orw)

    colidx = np.concatenate(cols)
    values = np.concatenate(vals)
    rowloc = np.concatenate(rlocs)
    out_row = np.concatenate(orows)

    B = colidx.shape[0]
    if pad_blocks_to is not None and pad_blocks_to > B:
        pad = pad_blocks_to - B
        colidx = np.pad(colidx, ((0, pad), (0, 0)))
        values = np.pad(values, ((0, pad), (0, 0)))
        rowloc = np.pad(rowloc, ((0, pad), (0, 0)), constant_values=R - 1)
        out_row = np.pad(out_row, ((0, pad), (0, 0)), constant_values=n_out)

    merged = {"colidx": colidx, "values": values, "rowloc": rowloc,
              "out_row": out_row, "R": R, "C": C}
    if all("nnz" in s for s in slab_list):
        nnz = np.concatenate([np.asarray(s["nnz"], np.int32)
                              for s in slab_list])
        merged["nnz"] = np.pad(nnz, (0, colidx.shape[0] - len(nnz)))
    return merged, out_offsets, col_offsets, n_out


def _merge_heads(heads: Sequence[HeadValues], blocks: Sequence[int],
                 b_total: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-block head kinds and slopes ``[b_total, H]``: each graph's rows
    repeated over its blocks, bucket-padding blocks ``NONE``."""
    H = len(heads[0].kind)
    kind = np.full((b_total, H), NONE, np.int32)
    slope = np.zeros((b_total, H), np.float32)
    start = 0
    for hv, b in zip(heads, blocks):
        kind[start:start + b] = hv.kind
        slope[start:start + b] = hv.slope
        start += b
    return kind, slope


_PALLAS_KERNELS = {
    "resident": spmm_block_slabs,
    "windowed": spmm_block_slabs_windowed,
    "hbm": spmm_block_slabs_hbm,
}


def spmm_batched(
    slab_list: Sequence[Dict],
    x_list: Sequence[jax.Array],
    n_rows_list: Sequence[int],
    *,
    backend: str = "pallas",
    pad_blocks_to: Optional[int] = None,
    return_decision: bool = False,
    phases: Optional[Dict[str, float]] = None,
    heads: Optional[Sequence[HeadValues]] = None,
) -> List[jax.Array] | Tuple[List[jax.Array], Optional[RoutingDecision]]:
    """Fused SpMM over several graphs; returns one ``[n_rows_g, F_g]`` output
    per graph (degree-sorted row order, same as the single-graph kernel).

    Feature matrices may differ in width; they are right-padded to the batch
    max ``F`` (padding columns are sliced off on the way out).

    Backends: ``auto`` routes the merged dispatch (resident / windowed /
    hbm) by VMEM footprint; ``pallas`` forces the resident kernel and raises
    ``VmemBudgetError`` when the concatenated features exceed its budget;
    ``windowed`` / ``hbm`` force those variants; ``blocked`` is the portable
    jnp twin. With ``return_decision=True`` the routing record (or ``None``
    for ``blocked``) comes back alongside the outputs.

    ``heads`` (one :class:`HeadValues` per graph, all with the same H;
    each slab dict then carries ``nnz``) makes the dispatch multi-head:
    every ``x`` is H heads of ``F / H`` columns (a whole number of 128-lane
    tiles for the Pallas kernels) and the values of each head come from its
    kind.

    The body runs as the ``gcn.dispatch`` phases ``merge`` (host slab
    merge), ``prepare`` (feature concat), ``upload`` (the four merged slab
    arrays to the device), ``scores`` (with ``heads``: the asynchronous
    launch of the per-call values) and ``launch`` (route and the
    asynchronous kernel call), each a span of :mod:`repro.core.spans`;
    ``phases``, if given, is the caller's dict that gains each phase's
    seconds.
    """
    G = len(slab_list)
    assert G == len(x_list) == len(n_rows_list) and G > 0
    if backend not in ("pallas", "windowed", "hbm", "auto", "blocked"):
        raise ValueError(f"batched spmm backend must be "
                         f"auto|pallas|windowed|hbm|blocked, got {backend!r}")
    n_cols_list = [int(x.shape[0]) for x in x_list]
    f_list = [int(x.shape[1]) for x in x_list]
    F = max(f_list)

    with span(MERGE, phases):
        merged, out_off, _, n_out = batch_graph_slabs(
            slab_list, list(n_rows_list), n_cols_list,
            pad_blocks_to=pad_blocks_to)
        if heads is not None:
            kind, slope = _merge_heads(
                heads, [int(np.shape(s["colidx"])[0]) for s in slab_list],
                merged["colidx"].shape[0])

    with span(PREPARE, phases):
        x_cat = jnp.concatenate(
            [jnp.pad(jnp.asarray(x, dtype=jnp.float32),
                     ((0, 0), (0, F - f))) if f < F
             else jnp.asarray(x, dtype=jnp.float32)
             for x, f in zip(x_list, f_list)], axis=0)
        if heads is not None:
            el = jnp.concatenate([jnp.asarray(h.el, jnp.float32)
                                  for h in heads])
            er = jnp.concatenate([jnp.asarray(h.er, jnp.float32)
                                  for h in heads]
                                 + [jnp.zeros((1, kind.shape[1]),
                                              jnp.float32)])

    with span(UPLOAD, phases):
        slabs = [jnp.asarray(merged[k])
                 for k in ("colidx", "values", "rowloc", "out_row")]
        if heads is not None:
            per_call = [jnp.asarray(a) for a in (merged["nnz"], kind, slope)]

    if heads is not None:
        with span(SCORES, phases):
            slabs[1] = edge_softmax_slabs(*slabs, *per_call, el, er)

    decision: Optional[RoutingDecision] = None
    with span(LAUNCH, phases):
        if backend == "blocked":
            from .ops import spmm_blocked  # deferred: ops re-exports this
            out = spmm_blocked(*slabs, x_cat, n_out)
        else:
            force = {"pallas": "resident",
                     "windowed": "windowed", "hbm": "hbm"}.get(backend)
            # sum of n_cols: the quantity that overflows the resident tile
            decision = route_spmm(int(x_cat.shape[0]), F, int(merged["C"]),
                                  int(merged["R"]), force=force)
            out = _PALLAS_KERNELS[decision.backend](*slabs, x_cat, n_out)
        outs = [out[int(out_off[i]):int(out_off[i + 1]), :f_list[i]]
                for i in range(G)]
    return (outs, decision) if return_decision else outs
