"""Backend routing for the Accel-GCN SpMM kernels: pick the execution
strategy from the workload, not at build time.

The block-level partition fixes *how nonzeros are grouped*; it does not fix
*where the dense feature matrix lives*. Three kernel regimes exist (AWB-GCN
makes the same runtime-adaptation argument for varying workloads):

  regime      X placement                     per-grid-step VMEM cost
  ----------  ------------------------------  ------------------------------
  resident    whole [N_pad, f_tile] in VMEM   N_pad * f_tile * itemsize
  windowed    [window_rows, f_tile] window,   window_rows * f_tile * itemsize
              accumulated over num_windows      (x num_windows grid sweeps)
  hbm         X stays in HBM; C rows gathered C * W * itemsize scratch,
              per block by one-row DMAs at      W = hbm_gather_width(F_pad)
              gather width W                    (F_pad up to 2048 at C=256)

This module owns the arithmetic: a per-dispatch VMEM footprint estimate from
``(N_pad, F_pad, C, R, f_tile)``, the HBM kernel's gather width
(:func:`hbm_gather_width`) and a :func:`route_spmm` that picks the
cheapest regime that fits the budget. Callers that *force* the resident
kernel on an oversized dispatch get an explicit :class:`VmemBudgetError`
at trace time instead of a silent interpret-mode slowdown that would be a
compile failure on real hardware.

Default thresholds (f32, f_tile=128, budget 2 MiB for the X tile):

  N_pad <= 4096           -> resident   (X tile <= 2 MiB)
  N_pad <= 4 * 4096       -> windowed   (<= MAX_WINDOWS full-grid sweeps)
  N_pad >  16384          -> hbm        (gather cost ~ nnz, independent of N)
"""
from __future__ import annotations

import dataclasses
import math

__all__ = [
    "VMEM_BYTES_PER_CORE",
    "X_TILE_BUDGET_BYTES",
    "TOTAL_VMEM_BUDGET_BYTES",
    "MAX_WINDOWS",
    "VmemBudgetError",
    "RoutingDecision",
    "pad_rows",
    "pad_features",
    "resident_window_rows",
    "hbm_gather_width",
    "estimate_vmem_bytes",
    "route_spmm",
    "assert_resident_fits",
    "FleetDecision",
    "route_fleet",
]

# TPU cores expose ~16 MiB of VMEM. Mosaic double-buffers every streamed
# block, the epilogue needs headroom, and the MXU operands (one-hot,
# gathered slab) live there too — so the X feature tile gets a 2 MiB
# PER-BUFFER slice, which at f32 x 128 lanes is the documented N_pad <=
# 4096 comfort zone of the resident kernel, and the total per-step
# footprint (all buffers of all operands) must stay within half the core.
#
# Note the windowed regime's total footprint (~4.4 MiB: two window buffers
# in flight) exceeds what a resident tile would cost for 4096 < N_pad <=
# 8192 — it is still the right call there because the compiled tile shape
# stays FIXED at [window, f_tile] for the whole regime (one jit cache entry
# serves any N; a budget-sized resident tile would recompile per N bucket
# and grow without bound), while everything stays under the total budget.
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024
X_TILE_BUDGET_BYTES = 2 * 1024 * 1024
TOTAL_VMEM_BUDGET_BYTES = VMEM_BYTES_PER_CORE // 2

# Each window is a full extra sweep of the (B, nf) grid; past a few windows
# the dead-gather work grows linearly with N while the HBM kernel's DMA cost
# stays proportional to nnz, so cap the windowed regime.
MAX_WINDOWS = 4

_SUBLANE = 8  # f32 sublane quantum: row counts pad to multiples of this


class VmemBudgetError(ValueError):
    """A forced-resident dispatch whose X tile exceeds the VMEM budget.

    Raised at trace time — on hardware the same call would be a Mosaic
    compile failure (or an OOM), not a graceful slowdown.
    """


def pad_rows(n: int) -> int:
    """Rows pad to the f32 sublane quantum (8)."""
    return ((int(n) + _SUBLANE - 1) // _SUBLANE) * _SUBLANE


def pad_features(f: int, f_tile: int) -> int:
    """Features pad to full 128-lane tiles (the combined-warp quantum)."""
    return max(f_tile, ((int(f) + f_tile - 1) // f_tile) * f_tile)


def resident_window_rows(f_tile: int = 128, itemsize: int = 4,
                         budget_bytes: int = X_TILE_BUDGET_BYTES) -> int:
    """Largest sublane-aligned row count whose X tile fits the budget.

    This is both the resident-regime cap and the window height of the
    windowed kernel (4096 at f32/128-lane defaults).
    """
    rows = budget_bytes // (f_tile * itemsize)
    return max(_SUBLANE, (rows // _SUBLANE) * _SUBLANE)


def hbm_gather_width(f_pad: int, C: int, itemsize: int = 4) -> int:
    """Feature width W of the HBM kernel's row gather: the widest multiple
    of 128 lanes that divides ``f_pad`` and whose ``[C, W]`` gather scratch
    fits ``X_TILE_BUDGET_BYTES`` (128 if none does). Each block gathers its
    rows ``f_pad / W`` times; at C=256, f32, once up to F_pad = 2048.
    """
    widths = [w for w in range(128, f_pad + 1, 128)
              if f_pad % w == 0 and C * w * itemsize <= X_TILE_BUDGET_BYTES]
    return max(widths, default=128)


def estimate_vmem_bytes(backend: str, n_pad: int, C: int, R: int,
                        *, f_tile: int = 128, itemsize: int = 4,
                        window_rows: int | None = None,
                        f_pad: int | None = None) -> int:
    """Per-grid-step VMEM footprint estimate of one SpMM dispatch.

    Counts the X tile (regime-dependent), the double-buffered slab metadata
    and output block, and the MXU operands (gathered slab + one-hot). The
    grid dimensions (B blocks x feature tiles) multiply the step *count*,
    not the per-step footprint, so they do not appear here. The resident
    and windowed kernels step over ``f_tile``-wide feature tiles; the HBM
    kernel gathers at ``hbm_gather_width(f_pad)`` (``f_pad`` defaults to
    one tile) straight into its slab, so X costs it no VMEM of its own.
    """
    meta = 2 * 3 * C * 4            # colidx/values/rowloc, double-buffered
    onehot = C * R * 4              # [R, C] segment-reduction operand
    if backend == "resident":
        x_cost = n_pad * f_tile * itemsize
    elif backend == "windowed":
        w = window_rows or resident_window_rows(f_tile, itemsize)
        x_cost = 2 * min(n_pad, w) * f_tile * itemsize  # streamed -> 2 bufs
    elif backend == "hbm":
        width = hbm_gather_width(f_pad or f_tile, C, itemsize)
        return (meta + onehot
                + 2 * R * width * 4          # output block, double-buffered
                + C * width * itemsize)      # [C, W] gathered slab, X's dtype
    else:
        raise ValueError(f"unknown backend {backend!r}")
    out = 2 * R * f_tile * 4        # output block, double-buffered
    gathered = C * f_tile * 4       # [C, f_tile] f32 slab feeding the MXU
    return x_cost + meta + out + gathered + onehot


@dataclasses.dataclass(frozen=True)
class RoutingDecision:
    """One dispatch's routing outcome (also the stats/logging record)."""

    backend: str          # "resident" | "windowed" | "hbm"
    n_rows: int           # unpadded X rows of the dispatch (sum over batch)
    n_pad: int
    f_pad: int
    C: int
    R: int
    f_tile: int
    itemsize: int
    num_windows: int      # 1 for resident; >1 windowed; 0 for hbm
    window_rows: int
    gather_width: int     # columns per row copy: f_tile, or (hbm)
                          # hbm_gather_width; f_pad / it gather passes
    vmem_bytes: int       # total per-step estimate for the chosen backend
    resident_bytes: int   # what the forced-resident tile would have cost
    budget_bytes: int     # per-buffer X-tile budget (resident/window cap)
    total_budget_bytes: int   # whole-step cap every regime must satisfy
    reason: str

    def describe(self) -> str:
        return (f"{self.backend}: N_pad={self.n_pad} F_pad={self.f_pad} "
                f"C={self.C} R={self.R} vmem~{self.vmem_bytes / 1024:.0f}KiB "
                f"({self.reason})")


def route_spmm(n_x_rows: int, n_features: int, C: int, R: int,
               *, f_tile: int = 128, itemsize: int = 4,
               budget_bytes: int = X_TILE_BUDGET_BYTES,
               max_windows: int = MAX_WINDOWS,
               force: str | None = None) -> RoutingDecision:
    """Pick the kernel regime for one dispatch.

    ``n_x_rows`` is the row count of the dense feature operand — for a
    batched dispatch that is ``sum(n_cols_g)`` of the concatenated batch,
    which is exactly how a batch of small graphs can overflow a budget each
    graph individually respects.

    Routing picks the first of resident -> windowed -> hbm whose X-tile
    constraint holds AND whose whole-step estimate fits the total VMEM
    budget; the MXU operands (one-hot ``[R, C]``, gathered ``[C, W]`` with
    ``W >= f_tile``) grow with C in every regime, so a partition capacity so
    large that even the HBM regime overflows raises :class:`VmemBudgetError`
    (the fix is a smaller ``max_block_warps x max_warp_nzs``, not a
    different kernel).

    ``force="resident"`` validates instead of routing: it raises
    :class:`VmemBudgetError` when the dispatch does not fit, making the
    failure mode of ``backend="pallas"`` explicit. ``force="windowed"`` /
    ``force="hbm"`` always succeed (both regimes are N-unbounded; windowed
    just degrades past ``max_windows`` sweeps) — forcing is the explicit
    escape hatch, so only the router-chosen path enforces the total budget.
    """
    n_pad = pad_rows(n_x_rows)
    f_pad = pad_features(n_features, f_tile)
    window = resident_window_rows(f_tile, itemsize, budget_bytes)
    resident_bytes = estimate_vmem_bytes(
        "resident", n_pad, C, R, f_tile=f_tile, itemsize=itemsize)

    def _decision(backend: str, num_windows: int, reason: str) -> RoutingDecision:
        return RoutingDecision(
            backend=backend, n_rows=int(n_x_rows), n_pad=n_pad, f_pad=f_pad,
            C=int(C), R=int(R), f_tile=f_tile, itemsize=itemsize,
            num_windows=num_windows, window_rows=window,
            gather_width=(hbm_gather_width(f_pad, C, itemsize)
                          if backend == "hbm" else f_tile),
            vmem_bytes=estimate_vmem_bytes(
                backend, n_pad, C, R, f_tile=f_tile, itemsize=itemsize,
                window_rows=window, f_pad=f_pad),
            resident_bytes=resident_bytes, budget_bytes=budget_bytes,
            total_budget_bytes=TOTAL_VMEM_BUDGET_BYTES,
            reason=reason)

    if force is not None:
        if force == "resident":
            if n_pad > window:
                suggested = route_spmm(
                    n_x_rows, n_features, C, R, f_tile=f_tile,
                    itemsize=itemsize, budget_bytes=budget_bytes,
                    max_windows=max_windows).backend
                raise VmemBudgetError(
                    f"resident SpMM kernel forced on an oversized dispatch: "
                    f"X tile [N_pad={n_pad}, f_tile={f_tile}] x {itemsize}B "
                    f"= {n_pad * f_tile * itemsize / 1024:.0f} KiB exceeds "
                    f"the {budget_bytes // 1024} KiB VMEM budget "
                    f"(N_pad <= {window} fits; F_pad={f_pad}, C={C}, R={R}). "
                    f"Use backend='auto' or the '{suggested}' backend for "
                    f"this shape.")
            return _decision("resident", 1, "forced")
        if force == "windowed":
            return _decision(
                "windowed", max(1, math.ceil(n_pad / window)), "forced")
        if force == "hbm":
            return _decision("hbm", 0, "forced")
        raise ValueError(f"unknown forced backend {force!r}")

    num_windows = max(1, math.ceil(n_pad / window))
    candidates = []
    if n_pad <= window:
        candidates.append(
            ("resident", 1, f"X tile fits VMEM budget (N_pad <= {window})"))
    elif num_windows <= max_windows:
        candidates.append(
            ("windowed", num_windows,
             f"{num_windows} row windows of {window} (<= {max_windows})"))
    if num_windows > max_windows:
        hbm_reason = (f"N_pad={n_pad} needs {num_windows} windows "
                      f"(> {max_windows}); per-block DMA gather scales with "
                      f"nnz, not N")
    else:
        hbm_reason = (f"leaner regimes exceed the total VMEM budget at "
                      f"C={C}, R={R}")
    candidates.append(("hbm", 0, hbm_reason))

    for backend, nw, reason in candidates:
        if estimate_vmem_bytes(backend, n_pad, C, R, f_tile=f_tile,
                               itemsize=itemsize, window_rows=window,
                               f_pad=f_pad) <= TOTAL_VMEM_BUDGET_BYTES:
            return _decision(backend, nw, reason)
    hbm_bytes = estimate_vmem_bytes("hbm", n_pad, C, R, f_tile=f_tile,
                                    itemsize=itemsize, f_pad=f_pad)
    raise VmemBudgetError(
        f"no SpMM regime fits the total VMEM budget "
        f"({TOTAL_VMEM_BUDGET_BYTES // 1024} KiB): block capacity C={C}, "
        f"R={R} costs {hbm_bytes // 1024} KiB per grid step even with X in "
        f"HBM (one-hot [R, C] and gathered [C, "
        f"{hbm_gather_width(f_pad, C, itemsize)}] MXU operands); "
        f"repartition with a smaller "
        f"max_block_warps x max_warp_nzs.")


@dataclasses.dataclass(frozen=True)
class FleetDecision:
    """One dispatch's *fleet* routing outcome: how many devices it spans and
    how each device's share executes.

    ``per_device`` is the :class:`RoutingDecision` for ONE device's slice of
    the work (the whole dispatch for ``strategy="single"``); ``single`` is
    what one device alone would have run — keeping both makes the win
    legible in logs ("windowed alone, resident per-device once feature-
    sharded 8 ways"). ``n_hosts`` > 1 marks a GLOBAL-mesh dispatch: the
    devices span several processes and execution is SPMD-collective.
    """

    strategy: str             # "single" | "feature" | "block"
    n_devices: int            # devices the dispatch spans (1 for single)
    per_device: RoutingDecision
    single: RoutingDecision
    num_blocks: int
    reason: str
    n_hosts: int = 1          # processes the devices span (1 == one host)

    def describe(self) -> str:
        span = (f"x{self.n_devices}dev/{self.n_hosts}host"
                if self.n_hosts > 1 else f"x{self.n_devices}")
        return (f"{self.strategy}{span}: "
                f"per-device {self.per_device.backend} ({self.reason})")


def route_fleet(n_x_rows: int, n_features: int, C: int, R: int,
                num_blocks: int, n_devices: int,
                *, f_tile: int = 128, itemsize: int = 4,
                min_blocks_per_device: int = 4,
                n_hosts: int = 1) -> FleetDecision:
    """Pick single-device vs feature-sharded vs block-sharded execution.

    ``n_hosts > 1`` routes over the GLOBAL mesh (``n_devices`` then counts
    every process's devices). Two things change at host granularity:

    * **feature sharding is disabled** — its output comes back
      column-sharded across *hosts*, so every answer would pay a
      cross-host gather on the serving path; the per-request win the
      zero-communication column split buys within one host inverts once
      DCN sits between the shards. Wide dispatches stay single-host
      (the placement directory's owner serves them).
    * **block sharding stays eligible** — its ``psum`` combine returns a
      fully-replicated result on every host (each participant reads its
      answer locally), which is exactly the collective a giant graph
      must pay anyway to exceed one host's memory. The block threshold
      still applies per GLOBAL device.

    The fleet's aggregate VMEM/HBM budget is the single-device budget times
    the device count, and the two sharding strategies spend it differently:

    * **feature** — the paper's column-dimension parallelism at device
      granularity: each device owns ``F_pad / n_devices`` feature columns
      and runs the FULL block schedule on them. Zero cross-device
      communication; per-device grid steps (and the per-device slice of X)
      shrink by the device count. Chosen whenever the padded feature width
      carries at least one full ``f_tile`` per device — otherwise some
      devices would idle.
    * **block** — for one giant graph with narrow features — "giant"
      meaning the single-device VMEM estimate already demoted it off the
      resident regime: the partition's blocks go round-robin across devices
      (degree-sorted emission order means heavy blocks interleave, the
      AWB-GCN balancing argument), X is replicated/all-gathered, and
      per-device partial row results psum back. Needs enough blocks
      (``min_blocks_per_device`` per device) to be worth the collective.
    * **single** — everything else: a dispatch that fits one device's VMEM
      budget as a resident tile with narrow features gains nothing from the
      mesh; splitting it would trade zero VMEM pressure for collective and
      launch overhead.

    The per-device regime (resident / windowed / hbm) is still
    :func:`route_spmm` on the per-device share — feature sharding does not
    change the X *row* count, so a dispatch that is windowed alone stays
    windowed per device, just with 1/n-th of the feature sweeps.
    """
    if n_hosts < 1:
        raise ValueError(f"n_hosts must be >= 1, got {n_hosts}")
    single = route_spmm(n_x_rows, n_features, C, R,
                        f_tile=f_tile, itemsize=itemsize)
    if n_devices <= 1:
        return FleetDecision("single", 1, single, single, num_blocks,
                             "one device")
    f_pad = pad_features(n_features, f_tile)
    f_tiles = f_pad // f_tile
    if f_tiles >= n_devices and n_hosts == 1:
        per = route_spmm(n_x_rows, f_pad // n_devices, C, R,
                         f_tile=f_tile, itemsize=itemsize)
        return FleetDecision(
            "feature", n_devices, per, single, num_blocks,
            f"{f_tiles} feature tiles over {n_devices} devices: "
            f"zero-communication column split, per-device F="
            f"{f_pad // n_devices}")
    if (single.backend != "resident"
            and num_blocks >= min_blocks_per_device * n_devices):
        # per-step footprint is block-count-independent: one device's share
        # routes exactly like the whole dispatch, with B/n grid steps
        span = (f"{n_devices} devices"
                if n_hosts == 1 else
                f"{n_devices} devices on {n_hosts} hosts (global mesh, "
                f"SPMD-collective)")
        feat_note = (
            f"features are narrow ({f_tiles} tile(s) < {n_devices} devices)"
            if f_tiles < n_devices else
            f"feature split is disabled across {n_hosts} hosts "
            f"({f_tiles} tiles would shard, but column-split answers pay "
            f"a cross-host gather)")
        return FleetDecision(
            "block", n_devices, single, single, num_blocks,
            f"single-device estimate demotes to {single.backend} and "
            f"{feat_note}: {num_blocks} blocks round-robin over {span}, "
            f"X replicated, partials psum", n_hosts=n_hosts)
    why_not_feature = ("" if f_tiles < n_devices else
                       "; feature split skipped: cross-host column "
                       "gather would tax every answer")
    return FleetDecision(
        "single", 1, single, single, num_blocks,
        f"{single.backend} on one device ({f_tiles} feature tile(s), "
        f"{num_blocks} block(s)): sharding would cost more than it "
        f"saves{why_not_feature}")


def assert_resident_fits(n_x_rows: int, n_features: int, C: int, R: int,
                         *, f_tile: int = 128, itemsize: int = 4,
                         budget_bytes: int = X_TILE_BUDGET_BYTES) -> None:
    """Raise :class:`VmemBudgetError` unless the resident X tile fits."""
    route_spmm(n_x_rows, n_features, C, R, f_tile=f_tile, itemsize=itemsize,
               budget_bytes=budget_bytes, force="resident")
