"""Device-partitioned plan cache: each partition plan resident on ONE device.

A single host's :class:`~repro.core.plan_cache.PlanCache` caps the serving
working set at what one device's HBM holds. :class:`FleetPlanCache` wraps a
per-device shard of ``PlanCache`` behind a placement policy so the fleet's
aggregate plan capacity grows with device count:

* **consistent-hash placement** — a graph's content hash lands on a hash
  ring (:class:`ConsistentHashRing`, virtual nodes per device), so the same
  graph always lands on the same device across processes and restarts, and
  resizing the fleet remaps only ~1/d of the keys;
* **load-aware override** — when the ring's choice is already far fuller
  than the emptiest shard (more than ``load_spread`` plans apart), the plan
  goes to the least-loaded shard instead. Placements are sticky: once a key
  is placed, later lookups go to the recorded shard, so the override never
  strands a cached plan.

Staging: the owning shard's plans have their device arrays ``device_put``
onto the owning device, so a fleet dispatch reads slabs from local memory —
the plan is *resident on exactly one device* by default. Hot plans can be
**replicated**: :meth:`FleetPlanCache.add_replica` stages an independent
copy of the primary's plan on another device's shard (independent because
``_ensure_staged`` mutates plans in place — a shared object would yank the
primary's slabs off its device), and :meth:`FleetPlanCache.drop_replica`
demotes a cold copy. The primary placement is never dropped by demotion.
"""
from __future__ import annotations

import bisect
import dataclasses
import hashlib
import threading
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax

from ..core.graph import CSRGraph
from ..core.plan_cache import (
    PartitionConfig, PartitionPlan, PlanCache, graph_content_hash,
    build_partition_plan,
)

__all__ = ["ConsistentHashRing", "FleetPlanCache"]


class ConsistentHashRing:
    """Classic consistent-hash ring over integer member ids.

    ``vnodes`` virtual points per member smooth the arc lengths; lookup is
    a bisect over the sorted point list. Members are the fleet's device
    indices — adding/removing a device moves only the keys on its arcs.

    ``labels`` optionally names each member's ring points (same length as
    ``members``). Point positions depend only on the label, so a caller
    whose member ids are *indices into a mutable slot table* (the
    cross-host placement directory) keeps surviving keys stationary when
    the table shrinks: rebuild the ring with the surviving labels and only
    the removed member's arcs move.
    """

    def __init__(self, members: Sequence[int], vnodes: int = 64,
                 labels: Optional[Sequence[str]] = None):
        members = list(members)
        if not members:
            raise ValueError("hash ring needs >= 1 member")
        if labels is not None and len(labels) != len(members):
            raise ValueError(
                f"{len(labels)} labels for {len(members)} members")
        self.vnodes = vnodes
        self._points: List[Tuple[int, int]] = []
        for j, m in enumerate(members):
            label = labels[j] if labels is not None else f"dev{m}"
            for v in range(vnodes):
                h = hashlib.blake2b(f"{label}#v{v}".encode(),
                                    digest_size=8).digest()
                self._points.append((int.from_bytes(h, "big"), int(m)))
        self._points.sort()
        self._keys = [p[0] for p in self._points]

    def lookup(self, key: str) -> int:
        """Member owning ``key`` (first ring point clockwise of its hash)."""
        h = int.from_bytes(
            hashlib.blake2b(key.encode(), digest_size=8).digest(), "big")
        i = bisect.bisect_right(self._keys, h) % len(self._points)
        return self._points[i][1]


class FleetPlanCache:
    """Per-device :class:`PlanCache` shards behind one placement policy.

    Drop-in for the single ``PlanCache`` where the serving engine is
    concerned (``get_or_build`` / ``get_by_key`` / ``stats`` / ``builds``…),
    plus :meth:`device_index_of` so the fleet engine can group dispatches
    by owning device. ``capacity_per_device`` bounds each shard, so total
    fleet capacity is ``capacity_per_device * len(devices)``.
    """

    def __init__(self, devices: Optional[Sequence] = None, *,
                 capacity_per_device: int = 32,
                 load_spread: int = 4,
                 vnodes: int = 64,
                 save_dir: Optional[str] = None):
        self.devices = list(devices if devices is not None else jax.devices())
        if not self.devices:
            raise ValueError("FleetPlanCache needs >= 1 device")
        self.capacity_per_device = capacity_per_device
        self.load_spread = load_spread
        # shards share one spill dir: spill names are content-hashed, so a
        # plan evicted from shard 3 can be reloaded by any shard later
        self.shards: List[PlanCache] = [
            PlanCache(capacity_per_device, save_dir=save_dir)
            for _ in self.devices]
        self.ring = ConsistentHashRing(range(len(self.devices)), vnodes)
        self._lock = threading.Lock()
        self._placements: Dict[Tuple[str, PartitionConfig], int] = {}
        # keys whose build is in flight (placed, not yet inserted into the
        # owning shard): exempt from placement pruning, refcounted because
        # several threads can be waiting on one single-flight build
        self._building: Dict[Tuple[str, PartitionConfig], int] = {}
        # extra replica devices per key (primary NOT included); replicated
        # and pinned keys are exempt from placement pruning
        self._replicas: Dict[Tuple[str, PartitionConfig], List[int]] = {}
        self._pinned: Set[Tuple[str, PartitionConfig]] = set()
        # version pins route to the shard that was serving the key when its
        # first reader pinned it — the placement may be gone by unpin time
        # (publish retires superseded keys), so the shard is remembered here
        self._vpins: Dict[Tuple[str, PartitionConfig], int] = {}
        self.placement_overrides = 0   # load-aware departures from the ring
        self.replicas_added = 0
        self.replicas_removed = 0

    # ------------------------------------------------------------- placement
    def device_index_of(self, key: Tuple[str, PartitionConfig]) -> int:
        """Owning device index of ``key`` (placing it if never seen)."""
        with self._lock:
            return self._place_locked(key)

    def pin(self, key: Tuple[str, PartitionConfig], device_index: int) -> int:
        """Pre-record an externally-decided placement for ``key``.

        The cross-host placement directory decides (host, device) fleet-wide;
        the owning host pins the directory's *device* choice here so its
        local shard placement agrees with what every other host believes.
        Sticky like any other placement: an existing placement wins (the
        plan is already resident there) and is returned.
        """
        if not 0 <= device_index < len(self.devices):
            raise ValueError(
                f"pin({device_index}) outside the {len(self.devices)}-device "
                f"fleet")
        with self._lock:
            self._pinned.add(key)
            return self._placements.setdefault(key, int(device_index))

    def _place_locked(self, key: Tuple[str, PartitionConfig]) -> int:
        dev = self._placements.get(key)
        if dev is not None:
            return dev
        dev = self.ring.lookup(key[0])
        sizes = [len(s) for s in self.shards]
        least = min(range(len(sizes)), key=sizes.__getitem__)
        if sizes[dev] - sizes[least] > self.load_spread:
            dev = least
            self.placement_overrides += 1
        self._placements[key] = dev
        # stickiness only matters while the plan is resident: once the
        # placement map outgrows the fleet's live capacity, drop entries
        # whose plan the owning shard has since evicted. A later lookup
        # re-places them with CURRENT load data (and this bounds the map
        # under one-off-graph churn instead of leaking per distinct graph).
        cap = 2 * self.capacity_per_device * len(self.shards)
        if len(self._placements) > cap:
            # exempt the key just placed and every in-flight build: their
            # plans have not been inserted into the owning shard yet, and a
            # pruned-mid-build placement would re-place later (possibly on
            # another shard) leaving a duplicate resident copy. Also exempt
            # pinned keys (the cross-host directory dictated their device —
            # re-placing would disagree with every other host) and keys with
            # a resident copy on ANY replica shard, not just the primary:
            # dropping the placement of a replicated key would strand its
            # replica copies and double-stage the plan on re-lookup.
            self._placements = {
                k: d for k, d in self._placements.items()
                if k == key or k in self._building or k in self._pinned
                or k in self.shards[d]
                or any(k in self.shards[r]
                       for r in self._replicas.get(k, ()))}
        return dev

    # -------------------------------------------------------------- replicas
    def replica_devices(self, key: Tuple[str, PartitionConfig]) -> List[int]:
        """Device indices holding ``key``'s plan, primary first.

        Extras whose shard has since LRU-evicted the copy are lazily
        dropped. Does NOT place unseen keys — an unplaced key returns [].
        """
        with self._lock:
            primary = self._placements.get(key)
            if primary is None:
                return []
            extras = self._replicas.get(key)
            if extras:
                live = [d for d in extras if key in self.shards[d]]
                if len(live) != len(extras):
                    self.replicas_removed += len(extras) - len(live)
                    if live:
                        self._replicas[key] = live
                    else:
                        del self._replicas[key]
                extras = live
            return [primary] + list(extras or [])

    def add_replica(self, key: Tuple[str, PartitionConfig],
                    device_index: int) -> bool:
        """Stage an independent copy of ``key``'s plan on another device.

        The copy's slabs/inv_perm are ``device_put`` onto the target via a
        ``dataclasses.replace`` clone — the primary plan object is mutated
        in place by ``_ensure_staged``, so sharing it would move the
        primary's arrays. Idempotent; returns False when the primary has
        no resident plan to copy (nothing staged).
        """
        if not 0 <= device_index < len(self.devices):
            raise ValueError(
                f"add_replica({device_index}) outside the "
                f"{len(self.devices)}-device fleet")
        with self._lock:
            primary = self._placements.get(key)
            if primary is None or device_index == primary:
                return primary is not None and device_index == primary
            if device_index in self._replicas.get(key, ()):
                return True
        plan = self.shards[primary].lookup(key)
        if plan is None:
            return False
        device = self.devices[device_index]
        copy = dataclasses.replace(
            plan,
            slabs={k: (jax.device_put(v, device) if hasattr(v, "shape")
                       else v)
                   for k, v in plan.slabs.items()},
            inv_perm=jax.device_put(plan.inv_perm, device))
        self.shards[device_index].put(copy)
        with self._lock:
            lst = self._replicas.setdefault(key, [])
            if device_index not in lst:
                lst.append(device_index)
                self.replicas_added += 1
        return True

    def drop_replica(self, key: Tuple[str, PartitionConfig],
                     device_index: int) -> bool:
        """Demote one replica copy. The PRIMARY placement is never dropped
        here — demotion only trims extras, so a cold streak can never
        un-place a plan (use ``clear`` or shard eviction for that)."""
        with self._lock:
            lst = self._replicas.get(key)
            if not lst or device_index not in lst:
                return False
            lst.remove(device_index)
            if not lst:
                del self._replicas[key]
            self.replicas_removed += 1
        self.shards[device_index].remove(key)
        return True

    def plan_on(self, key: Tuple[str, PartitionConfig],
                device_index: int) -> Optional[PartitionPlan]:
        """The resident plan copy on one specific shard (None if absent)."""
        return self.shards[device_index].lookup(key)

    # -------------------------------------------------------- version chain
    def pin_version(self, key: Tuple[str, PartitionConfig]) -> int:
        """Pin a reader's plan version on its serving shard (see
        :meth:`~repro.core.plan_cache.PlanCache.pin`). Returns the new
        refcount, or 0 when the key has no placement to pin against."""
        with self._lock:
            dev = self._vpins.get(key)
            if dev is None:
                dev = self._placements.get(key)
                if dev is None:
                    return 0
                self._vpins[key] = dev
        return self.shards[dev].pin(key)

    def unpin_version(self, key: Tuple[str, PartitionConfig]) -> int:
        """Release one reader pin (reclaims a retired version when the last
        pin drains). Routed by the shard remembered at pin time — the
        placement itself may already belong to a successor version."""
        with self._lock:
            dev = self._vpins.get(key)
        if dev is None:
            return 0
        c = self.shards[dev].unpin(key)
        if c == 0:
            with self._lock:
                self._vpins.pop(key, None)
        return c

    def retire(self, key: Tuple[str, PartitionConfig]) -> bool:
        """Retire a superseded key on EVERY shard (see
        :meth:`~repro.core.plan_cache.PlanCache.retire`) and drop its
        placement / replica / pin bookkeeping. The NON-owning hosts of a
        multihost mutation use this: they have no successor plan to
        publish locally, but a stale copy of the retired version (e.g. a
        replica staged onto this host) must not outlive its epoch. Returns
        True if any shard actually held the key."""
        any_retired = False
        for s in self.shards:
            any_retired = s.retire(key) or any_retired
        with self._lock:
            self._placements.pop(key, None)
            self._replicas.pop(key, None)
            self._pinned.discard(key)
        return any_retired

    def publish(self, plan: PartitionPlan, retire_key=None) -> PartitionPlan:
        """Publish the next version of a graph's plan fleet-wide (same
        shape as :meth:`PlanCache.publish`, which makes the serving
        engines' publish hook cache-agnostic):

        1. the new key inherits the retired key's PRIMARY device (sticky
           placement across versions — replicas, pinned directories, and
           warmed HBM stay meaningful), staged and inserted atomically on
           that shard;
        2. every replica device of the retired key gets a re-staged copy
           of the NEW version (hot graphs stay hot through a mutation);
        3. the retired key drops from every shard (parking per-shard where
           readers still pin it), its placement, replica list, and pin
           marker with it.
        """
        with self._lock:
            primary = None
            extras: List[int] = []
            if retire_key is not None:
                primary = self._placements.get(retire_key)
                extras = list(self._replicas.get(retire_key, ()))
            if primary is None:
                primary = self._place_locked(plan.key)
            else:
                self._placements[plan.key] = primary
            if retire_key in self._pinned:
                self._pinned.add(plan.key)
        staged = self._ensure_staged(plan, self.devices[primary])
        self.shards[primary].publish(staged)
        for dev in extras:
            self.add_replica(plan.key, dev)
        if retire_key is not None and retire_key != plan.key:
            for s in self.shards:
                s.retire(retire_key)
            with self._lock:
                self._placements.pop(retire_key, None)
                self._replicas.pop(retire_key, None)
                self._pinned.discard(retire_key)
        return staged

    # --------------------------------------------------------------- lookups
    def get_or_build(self, g: CSRGraph, cfg: PartitionConfig) -> PartitionPlan:
        key = (graph_content_hash(g), cfg)
        return self.get_by_key(
            key, lambda: build_partition_plan(g, cfg, graph_hash=key[0]))

    def get_by_key(self, key: Tuple[str, PartitionConfig],
                   build_fn: Callable[[], PartitionPlan]) -> PartitionPlan:
        # place AND register the in-flight build in ONE lock hold: a prune
        # racing between the two could otherwise drop the fresh placement
        # (key not yet in _building nor in any shard) and let a later
        # lookup re-place the key while the first copy builds — two
        # resident copies of one plan
        with self._lock:
            dev_idx = self._place_locked(key)
            self._building[key] = self._building.get(key, 0) + 1
        device = self.devices[dev_idx]
        try:
            plan = self.shards[dev_idx].get_by_key(key, build_fn)
        finally:
            with self._lock:
                n = self._building.get(key, 1) - 1
                if n <= 0:
                    self._building.pop(key, None)
                else:
                    self._building[key] = n
        return self._ensure_staged(plan, device)

    def lookup(self, key: Tuple[str, PartitionConfig]) -> Optional[PartitionPlan]:
        with self._lock:
            dev_idx = self._placements.get(key)
        if dev_idx is None:
            return None
        return self.shards[dev_idx].lookup(key)

    @staticmethod
    def _ensure_staged(plan: PartitionPlan, device) -> PartitionPlan:
        """Commit the plan's device arrays to the owning device (idempotent).

        Mutates the shared plan object in place: the staged arrays replace
        the unstaged ones for every holder, and re-staging an already-local
        array is a no-op transfer. Races between threads write equivalent
        values, so no lock is needed.
        """
        probe = plan.slabs["colidx"]
        if getattr(probe, "devices", lambda: None)() == {device}:
            return plan
        plan.slabs = {
            k: (jax.device_put(v, device) if hasattr(v, "shape") else v)
            for k, v in plan.slabs.items()}
        plan.inv_perm = jax.device_put(plan.inv_perm, device)
        return plan

    # ----------------------------------------------------------------- admin
    def __len__(self) -> int:
        return sum(len(s) for s in self.shards)

    def __contains__(self, key) -> bool:
        return any(key in s for s in self.shards)

    def clear(self) -> None:
        for s in self.shards:
            s.clear()
        with self._lock:
            self._placements.clear()
            self._replicas.clear()
            self._pinned.clear()

    def keys(self):
        out = []
        for s in self.shards:
            out.extend(s.keys())
        return out

    # aggregate counters, mirroring the PlanCache attribute API the tests
    # and engine use (reads are sums over shard snapshots)
    @property
    def builds(self) -> int:
        return sum(s.stats()["builds"] for s in self.shards)

    @property
    def hits(self) -> int:
        return sum(s.stats()["hits"] for s in self.shards)

    @property
    def misses(self) -> int:
        return sum(s.stats()["misses"] for s in self.shards)

    def stats(self) -> Dict[str, float]:
        """Aggregate counters + per-shard occupancy (for balance stats)."""
        per = [s.stats() for s in self.shards]
        agg: Dict[str, float] = {}
        for k in ("size", "lookups", "hits", "misses", "builds", "build_s",
                  "evictions", "spills", "disk_hits", "spill_rejects",
                  "device_bytes",
                  "publishes", "pins", "retired_versions",
                  "retired_reclaimed", "retired_live"):
            agg[k] = sum(p[k] for p in per)
        total = agg["hits"] + agg["misses"]
        agg["capacity"] = self.capacity_per_device * len(self.shards)
        agg["hit_rate"] = agg["hits"] / total if total else 0.0
        agg["devices"] = len(self.devices)
        agg["shard_sizes"] = [p["size"] for p in per]
        agg["shard_bytes"] = [p["device_bytes"] for p in per]
        with self._lock:
            agg["placements"] = len(self._placements)
            agg["placement_overrides"] = self.placement_overrides
            agg["replicated_keys"] = sum(
                1 for lst in self._replicas.values() if lst)
            agg["replica_copies"] = sum(
                len(lst) for lst in self._replicas.values())
            agg["replicas_added"] = self.replicas_added
            agg["replicas_removed"] = self.replicas_removed
            agg["pinned"] = len(self._pinned)
        return agg
