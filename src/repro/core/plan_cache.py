"""Partition-plan cache: amortize Accel-GCN preprocessing across requests.

The paper's block-level partition (§III-C) exists to cut per-inference
metadata overhead — but rebuilding the degree sort + pattern table + slab
packing on *every* call throws that win away in a serving setting where the
same graphs recur. This module factors the whole preprocessing pipeline into
a content-addressed :class:`PartitionPlan` and caches finished plans in an
LRU :class:`PlanCache` keyed by (graph content hash, partition config):

* ``graph_content_hash`` — blake2b over the CSR arrays (structure AND edge
  values), so A' and A'^T of the same graph, or the same topology with
  different normalization, get distinct plans;
* ``build_partition_plan`` — the one place the pipeline runs: degree sort ->
  Algorithm 1 pattern table -> Algorithm 2 block emission -> slab packing ->
  device staging. Everything downstream (AccelSpMM, the batched multi-graph
  path, GraphServeEngine) consumes plans;
* ``PlanCache`` — LRU with hit/miss/eviction counters and a ``builds``
  counter tests and the serving engine use to assert "partitioned exactly
  once per distinct (graph, config)".
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
from collections import OrderedDict
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import CSRGraph, degree_sort_csr
from .partition import (
    BlockPartition,
    block_level_partition,
    get_partition_patterns,
    pack_slabs,
    slab_layout_ok,
)
from .spans import PLAN_BUILD, span

__all__ = [
    "PartitionConfig",
    "PartitionPlan",
    "PlanCache",
    "graph_content_hash",
    "build_partition_plan",
]


@dataclasses.dataclass(frozen=True)
class PartitionConfig:
    """Static knobs that change the partition layout (part of the cache key):
    the partition mode and Algorithm 1's two bounds, from which the derived
    per-degree pattern table follows."""

    mode: str = "tpu"
    max_block_warps: int = 64
    max_warp_nzs: int = 4

    @property
    def deg_bound(self) -> int:
        return self.max_block_warps * self.max_warp_nzs


def graph_content_hash(g: CSRGraph) -> str:
    """Content hash of a CSR matrix: shapes, structure and edge values.

    Two graphs with the same topology but different values (e.g. before and
    after GCN normalization) hash differently — the packed slabs differ.
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(np.int64([g.n_rows, g.n_cols, g.nnz]).tobytes())
    h.update(np.ascontiguousarray(g.rowptr, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(g.colidx, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(g.values, dtype=np.float32).tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class PartitionPlan:
    """A finished, device-staged partition of one graph under one config.

    Immutable once built; shared freely between operators and serve batches.
    ``slabs`` holds the kernel inputs (colidx/values/rowloc/out_row as device
    arrays plus python ints R, C); ``inv_perm`` undoes the degree sort so
    callers always see the ORIGINAL row order.
    """

    key: Tuple[str, PartitionConfig]
    n_rows: int
    n_cols: int
    nnz: int
    slabs: Dict
    inv_perm: jax.Array          # original row -> sorted position
    partition: BlockPartition
    coo_row: jax.Array
    coo_col: jax.Array
    coo_val: jax.Array
    # monotone stamp in a graph's plan chain (0 = first build; incremental
    # repair / mutation bumps it — see core/plan_repair.py). The content
    # hash in ``key`` still changes with every version: the version is the
    # lineage, the hash is the identity.
    version: int = 0

    @property
    def graph_hash(self) -> str:
        return self.key[0]

    @property
    def config(self) -> PartitionConfig:
        return self.key[1]

    @property
    def num_blocks(self) -> int:
        return int(self.slabs["colidx"].shape[0])

    def device_bytes(self) -> int:
        """Approximate device footprint of the staged plan (for cache stats)."""
        total = 0
        for v in list(self.slabs.values()) + [self.inv_perm, self.coo_row,
                                              self.coo_col, self.coo_val]:
            if hasattr(v, "nbytes"):
                total += int(v.nbytes)
        return total


def build_partition_plan(g: CSRGraph, cfg: PartitionConfig,
                         graph_hash: Optional[str] = None) -> PartitionPlan:
    """Run the full O(n) preprocessing pipeline once and stage device buffers."""
    g.validate()
    gs = degree_sort_csr(g)
    pats = get_partition_patterns(
        cfg.max_block_warps, cfg.max_warp_nzs, mode=cfg.mode)
    bp = block_level_partition(gs, pats)
    slabs_np = pack_slabs(gs, bp)
    slabs = {k: jnp.asarray(v) for k, v in slabs_np.items()
             if isinstance(v, np.ndarray)}
    slabs["R"], slabs["C"] = slabs_np["R"], slabs_np["C"]

    inv_perm = np.empty(gs.n_rows, dtype=np.int64)
    inv_perm[gs.perm] = np.arange(gs.n_rows)

    # COO is cheap to keep and doubles as the gradient/baseline path.
    row_of = np.repeat(np.arange(g.n_rows, dtype=np.int32), np.diff(g.rowptr))
    return PartitionPlan(
        key=(graph_hash or graph_content_hash(g), cfg),
        n_rows=g.n_rows, n_cols=g.n_cols, nnz=g.nnz,
        slabs=slabs, inv_perm=jnp.asarray(inv_perm), partition=bp,
        coo_row=jnp.asarray(row_of),
        coo_col=jnp.asarray(g.colidx),
        coo_val=jnp.asarray(np.asarray(g.values, dtype=np.float32)),
    )


def _config_tag(cfg: PartitionConfig) -> str:
    """Stable short fingerprint of a PartitionConfig (part of spill names)."""
    h = hashlib.blake2b(repr(cfg).encode(), digest_size=8)
    return h.hexdigest()


class PlanCache:
    """LRU cache of :class:`PartitionPlan` keyed by (content hash, config).

    ``capacity`` counts plans, not bytes: partition metadata scales with nnz
    and serving workloads typically hold a small working set of graphs. All
    counters are monotone; ``stats()`` snapshots them.

    Thread safety: every lookup/insert/evict runs under one lock, so
    concurrent flush threads (the serving schedulers) can share a cache.
    Builds are *single-flight*: parallel ``get_or_build`` of the same
    (graph, config) runs the O(n) partition pipeline exactly once — the
    first caller builds (one ``miss`` + one ``build``), the rest wait on
    the in-flight build and then count as ``hits``. The build itself runs
    outside the cache lock, so distinct graphs still partition in parallel.

    Disk persistence (``save_dir``): evicted plans spill to
    ``<graph_hash>-<config_tag>.npz`` (content-hash-named — safe to share
    between processes serving the same graphs); a later miss reloads the
    spilled plan instead of re-running the partition pipeline. ``spills`` /
    ``disk_hits`` counters track both sides; a disk reload still counts as
    a ``miss`` but not as a ``build``. A spill whose slabs lack the layout
    the kernels fold (``partition.slab_layout_ok``), such as one written by
    an older plan repair, is deleted and rebuilt, counted in
    ``spill_rejects``.
    """

    def __init__(self, capacity: int = 32, save_dir: Optional[str] = None):
        if capacity < 1:
            raise ValueError("PlanCache capacity must be >= 1")
        self.capacity = capacity
        self.save_dir = save_dir
        if save_dir is not None:
            os.makedirs(save_dir, exist_ok=True)
        self._plans: "OrderedDict[Tuple[str, PartitionConfig], PartitionPlan]" = \
            OrderedDict()
        self._lock = threading.RLock()
        self._inflight: Dict[Tuple[str, PartitionConfig], threading.Event] = {}
        # version lifecycle: reader refcounts per key (a dispatch pins the
        # plan version it resolved for its whole duration) and retired
        # versions parked until their last pin drains
        self._pins: Dict[Tuple[str, PartitionConfig], int] = {}
        self._retired: Dict[Tuple[str, PartitionConfig], PartitionPlan] = {}
        self.lookups = 0        # == hits + misses, bumped under the SAME
        #                         lock hold (the stats-atomicity witness)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.builds = 0
        self.build_s = 0.0      # seconds in build_fn (the gcn.plan.build
        #                         span), disk reloads excluded
        self.spills = 0
        self.spill_rejects = 0      # spills reloaded with a foreign layout
        self.disk_hits = 0
        self.publishes = 0
        self.retired_versions = 0   # old versions parked behind live pins
        self.retired_reclaimed = 0  # parked versions whose pins drained

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._plans

    def get_or_build(self, g: CSRGraph, cfg: PartitionConfig) -> PartitionPlan:
        """Return the cached plan for (g, cfg), building it on first sight."""
        key = (graph_content_hash(g), cfg)
        return self.get_by_key(
            key, lambda: build_partition_plan(g, cfg, graph_hash=key[0]))

    def get_by_key(self, key: Tuple[str, PartitionConfig],
                   build_fn: Callable[[], PartitionPlan]) -> PartitionPlan:
        """Counter-tracked lookup for callers that already hold the key (the
        serving engine hashes each graph once at registration, not per
        request); ``build_fn`` runs only on a miss, and only in ONE thread
        when several miss the same key at once."""
        while True:
            with self._lock:
                plan = self._plans.get(key)
                if plan is not None:
                    self.hits += 1
                    self.lookups += 1
                    self._plans.move_to_end(key)
                    return plan
                pending = self._inflight.get(key)
                if pending is None:
                    event = threading.Event()
                    self._inflight[key] = event
                    self.misses += 1
                    self.lookups += 1
            if pending is not None:
                pending.wait()      # another thread is building this key;
                continue            # loop back — next pass is a hit
            try:
                plan = self._load_from_disk(key)
                built = plan is None
                timed: Dict[str, float] = {}
                if built:
                    with span(PLAN_BUILD, timed):
                        plan = build_fn()
                with self._lock:
                    if built:
                        self.builds += 1
                        self.build_s += timed[PLAN_BUILD]
                    else:
                        self.disk_hits += 1
                    evicted = self._insert_locked(key, plan)
                self._spill_evicted(evicted)
            finally:
                with self._lock:
                    del self._inflight[key]
                event.set()
            return plan

    def lookup(self, key: Tuple[str, PartitionConfig]) -> Optional[PartitionPlan]:
        """Counter-free peek (used by stats tooling); refreshes LRU order."""
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self._plans.move_to_end(key)
            return plan

    def put(self, plan: PartitionPlan) -> None:
        """Insert an externally-built plan (e.g. shipped from another host)."""
        with self._lock:
            evicted = self._insert_locked(plan.key, plan)
        self._spill_evicted(evicted)

    def _insert_locked(self, key, plan: PartitionPlan) -> list:
        """Insert under the lock; returns evicted plans for the caller to
        spill AFTER releasing it (an O(nnz) .npz write must not stall every
        concurrent lookup)."""
        if key in self._plans:
            self._plans.move_to_end(key)
        self._plans[key] = plan
        evicted = []
        while len(self._plans) > self.capacity:
            _, old = self._plans.popitem(last=False)
            self.evictions += 1
            evicted.append(old)
        return evicted

    def _spill_evicted(self, evicted: list) -> None:
        if self.save_dir is None:
            return
        for plan in evicted:
            if self._spill(plan):
                with self._lock:
                    self.spills += 1

    def remove(self, key) -> bool:
        """Drop one plan WITHOUT spilling it (replica demotion: another
        resident copy — and possibly a spilled .npz — still exists
        elsewhere). Returns True if the key was resident. Not counted as
        an eviction: the caller chose to drop it, capacity didn't."""
        with self._lock:
            return self._plans.pop(key, None) is not None

    # -------------------------------------------------------- version chain
    def pin(self, key) -> int:
        """A reader (one in-flight dispatch) holds this plan version: its
        key cannot be silently discarded by :meth:`retire` until the
        matching :meth:`unpin`. Returns the new refcount. Pin/unpin must
        balance — the concurrency tests assert refcounts drain to zero."""
        with self._lock:
            c = self._pins.get(key, 0) + 1
            self._pins[key] = c
            return c

    def unpin(self, key) -> int:
        """Release one reader pin; when the last pin of a RETIRED version
        drains, the parked plan is reclaimed. Returns the remaining count."""
        with self._lock:
            c = self._pins.get(key, 0) - 1
            if c > 0:
                self._pins[key] = c
                return c
            self._pins.pop(key, None)
            if self._retired.pop(key, None) is not None:
                self.retired_reclaimed += 1
            return 0

    def retire(self, key) -> bool:
        """Remove a superseded version from the serving set. Unpinned
        versions drop immediately (no spill — stale content must not be
        resurrected by a disk hit racing the publish); pinned versions PARK
        until their readers drain, so an in-flight dispatch keeps a
        reachable plan for its whole duration. Returns True if the key was
        resident or parked."""
        with self._lock:
            plan = self._plans.pop(key, None)
            if plan is None:
                return key in self._retired
            if self._pins.get(key, 0) > 0:
                self._retired[key] = plan
                self.retired_versions += 1
            return True

    # uniform names with FleetPlanCache (whose bare ``pin`` records
    # directory-dictated placements), so the engines stay cache-agnostic
    def pin_version(self, key) -> int:
        return self.pin(key)

    def unpin_version(self, key) -> int:
        return self.unpin(key)

    def publish(self, plan: PartitionPlan, retire_key=None) -> PartitionPlan:
        """Atomically make ``plan`` the current version and retire the one
        it supersedes: readers either resolve the old key (still parked if
        pinned) or the new one — never a torn in-between. Spilling of any
        capacity eviction happens outside the lock as usual."""
        with self._lock:
            evicted = self._insert_locked(plan.key, plan)
            if retire_key is not None and retire_key != plan.key:
                old = self._plans.pop(retire_key, None)
                if old is not None and self._pins.get(retire_key, 0) > 0:
                    self._retired[retire_key] = old
                    self.retired_versions += 1
            self.publishes += 1
        self._spill_evicted(evicted)
        return plan

    def apply_delta(self, key, g_old: CSRGraph, delta, *,
                    churn_threshold: float = 0.25):
        """Repair the plan under ``key`` for an edge delta and publish the
        next version in one step. ``g_old`` is the pre-delta graph the key
        was built from (rebuilt here if the plan was evicted meanwhile).
        Returns ``(g_new, PlanVersion)`` — the caller re-binds its
        graph_id to ``pv.plan.key`` and pushes the new graph content.
        """
        from .plan_repair import apply_and_repair   # circular at module load
        plan = self.get_by_key(
            key, lambda: build_partition_plan(g_old, key[1],
                                              graph_hash=key[0]))
        g_new, pv = apply_and_repair(plan, g_old, delta,
                                     churn_threshold=churn_threshold)
        self.publish(pv.plan, retire_key=key)
        return g_new, pv

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()

    def keys(self):
        with self._lock:
            return list(self._plans.keys())

    # ------------------------------------------------------------ disk spill
    def _spill_path(self, key: Tuple[str, PartitionConfig]) -> str:
        graph_hash, cfg = key
        return os.path.join(self.save_dir, f"{graph_hash}-{_config_tag(cfg)}.npz")

    def _spill(self, plan: PartitionPlan) -> bool:
        """Write an evicted plan as a content-hash-named .npz (atomic)."""
        path = self._spill_path(plan.key)
        if os.path.exists(path):
            return False        # same content already spilled (idempotent)
        bp = plan.partition
        payload = {
            "n_rows": np.int64(plan.n_rows),
            "n_cols": np.int64(plan.n_cols),
            "nnz": np.int64(plan.nnz),
            "version": np.int64(plan.version),
            "slab_R": np.int64(plan.slabs["R"]),
            "slab_C": np.int64(plan.slabs["C"]),
            "slab_colidx": np.asarray(plan.slabs["colidx"]),
            "slab_values": np.asarray(plan.slabs["values"]),
            "slab_rowloc": np.asarray(plan.slabs["rowloc"]),
            "slab_out_row": np.asarray(plan.slabs["out_row"]),
            "inv_perm": np.asarray(plan.inv_perm),
            "coo_row": np.asarray(plan.coo_row),
            "coo_col": np.asarray(plan.coo_col),
            "coo_val": np.asarray(plan.coo_val),
            "bp_meta": bp.meta,
            "bp_n_rows_blk": bp.n_rows_blk,
            "bp_nnz_blk": bp.nnz_blk,
            "bp_is_split": bp.is_split,
            "bp_n_rows": np.int64(bp.n_rows),
            "bp_nnz": np.int64(bp.nnz),
        }
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, **payload)
            os.replace(tmp, path)
            return True
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False

    def _load_from_disk(self, key: Tuple[str, PartitionConfig]
                        ) -> Optional[PartitionPlan]:
        """Reload a spilled plan; None when absent/unreadable or when its
        slab layout is not the one the kernels fold (then rebuild)."""
        if self.save_dir is None:
            return None
        path = self._spill_path(key)
        if not os.path.exists(path):
            return None
        _, cfg = key
        try:
            with np.load(path) as z:
                if not slab_layout_ok(z["slab_out_row"], int(z["n_rows"])):
                    with self._lock:
                        self.spill_rejects += 1
                    os.unlink(path)  # a fresh spill may take its name
                    return None
                slabs = {
                    "colidx": jnp.asarray(z["slab_colidx"]),
                    "values": jnp.asarray(z["slab_values"]),
                    "rowloc": jnp.asarray(z["slab_rowloc"]),
                    "out_row": jnp.asarray(z["slab_out_row"]),
                    "R": int(z["slab_R"]),
                    "C": int(z["slab_C"]),
                }
                bp = BlockPartition(
                    meta=z["bp_meta"],
                    n_rows_blk=z["bp_n_rows_blk"],
                    nnz_blk=z["bp_nnz_blk"],
                    is_split=z["bp_is_split"],
                    patterns=get_partition_patterns(
                        cfg.max_block_warps, cfg.max_warp_nzs, mode=cfg.mode),
                    n_rows=int(z["bp_n_rows"]),
                    nnz=int(z["bp_nnz"]),
                )
                return PartitionPlan(
                    key=key,
                    n_rows=int(z["n_rows"]), n_cols=int(z["n_cols"]),
                    nnz=int(z["nnz"]), slabs=slabs,
                    inv_perm=jnp.asarray(z["inv_perm"]), partition=bp,
                    coo_row=jnp.asarray(z["coo_row"]),
                    coo_col=jnp.asarray(z["coo_col"]),
                    coo_val=jnp.asarray(z["coo_val"]),
                    # pre-versioning spills reload as version 0
                    version=int(z["version"]) if "version" in z else 0,
                )
        except Exception:       # corrupt/partial/alien spill (BadZipFile,
            return None         # KeyError, OSError, ...): rebuild instead

    def stats(self) -> Dict[str, float]:
        """ATOMIC snapshot of every counter, taken under one lock hold.

        Guarantee: all values in one returned dict are from the same
        instant — a flush thread mutating counters mid-``stats()`` can
        never produce a torn read (e.g. ``hits + misses != lookups``, or a
        ``hit_rate`` computed from two different moments). The benchmark
        samplers and the fleet cache's per-shard aggregation rely on this.
        """
        with self._lock:
            total = self.hits + self.misses
            return {
                "size": len(self._plans),
                "capacity": self.capacity,
                "lookups": self.lookups,
                "hits": self.hits,
                "misses": self.misses,
                "builds": self.builds,
                "build_s": self.build_s,
                "evictions": self.evictions,
                "spills": self.spills,
                "disk_hits": self.disk_hits,
                "spill_rejects": self.spill_rejects,
                "publishes": self.publishes,
                "pins": sum(self._pins.values()),
                "retired_versions": self.retired_versions,
                "retired_reclaimed": self.retired_reclaimed,
                "retired_live": len(self._retired),
                "hit_rate": self.hits / total if total else 0.0,
                "device_bytes": sum(p.device_bytes()
                                    for p in self._plans.values()),
            }
