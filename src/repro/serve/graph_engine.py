"""Graph inference serving: continuous-batched, plan-cached SpMM dispatch.

Serving architecture (scheduler -> flush -> route -> dispatch)::

    callers ----- submit(graph_id, x) -> Future ------.
    threads ----- submit(graph_id, x) -> Future ------+--> BatchScheduler
    serve(reqs) - submit_many (sync wrapper) ---------'    admission queue
                                                               |
                               flush (size >= max_batch_requests, or the
                               oldest request is max_wait_ms old)
                                                               |
                                  _flush: group requests BY PLAN (graph),
                                  fuse same-graph features along the F
                                  axis, chunk distinct graphs into
                                  dispatches of <= max_graphs_per_batch
                                                               |
                                  _dispatch: merge slabs, bucket blocks,
                                  route by VMEM footprint (auto: resident /
                                  windowed / hbm), ONE fused pallas_call
                                                               |
                                  un-permute rows, split feature columns,
                                  item.complete(out) resolves each Future

The background admission queue is what makes batching *cross-caller*: the
old blocking ``serve()`` could only fuse requests its own caller had
already collected, so two concurrent callers never shared a dispatch and
the plan cache was touched from multiple threads without a lock. Now every
entry point funnels into one scheduler ( :mod:`repro.serve.scheduler` ),
requests on recurring graphs coalesce into fused dispatches no matter who
submitted them, and the (thread-safe) plan cache is read from the single
flush thread.

Tuning knobs:

* ``max_batch_requests`` / ``max_wait_ms`` — scheduler flush triggers.
  ``max_wait_ms`` bounds the co-batching wait of a lone request; under
  sustained load flushes are size-triggered and the knob is irrelevant.
* ``max_graphs_per_batch`` — distinct graphs fused into one kernel call
  (a flush larger than this becomes several dispatches, in arrival order).
* ``max_pending`` — admission bound; full queue blocks submitters
  (backpressure) or raises with ``submit(..., block=False)``.

A request may carry graph attention inputs (``submit(..., attention=(el,
er))``, GAT): per head, slot values computed per call from per-row scores
and softmaxed over each row's nonzeros (``kernels/edge_softmax.py``), then
the same fused slab kernels with one set of values per head. Attention
requests on one graph in one flush fuse as more heads, and the graph's plain
requests beside them as one more head with the plan's own values.

Per-request (enqueue->answer) latency comes from the scheduler's WorkItem
clock. Each dispatch's host wall time, from its start through
``block_until_ready`` (feature prep, slab merge, upload, launch and the wait
on the device, not the kernel alone), accumulates in ``total_serve_s``, and
by phase in ``dispatch_{prepare,merge,upload,scores,launch,wait}_s``;
``dispatch_answer_s`` times the un-permute and slicing after it. Each phase
is also a span on the profiler's clock (:mod:`repro.core.spans`).
``stats()`` merges engine counters, plan-cache counters (``cache_*``) and
scheduler counters (``sched_*``).
"""
from __future__ import annotations

import dataclasses
import logging
import math
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import CSRGraph, gcn_normalize
from ..core.plan_cache import (
    PartitionConfig, PartitionPlan, PlanCache,
    build_partition_plan, graph_content_hash,
)
from ..core import spans
from ..core.plan_repair import EdgeDelta, delta_chain_hash, repair_plan
from ..kernels.edge_softmax import ATTENTION, NONE, PLAIN, to_plan_order
from ..kernels.router import RoutingDecision, pad_features
from ..kernels.spmm_batched import HeadValues, bucket_blocks, spmm_batched
from ..tuning.tuner import PlanTuner
from ..tuning.search import TuningCandidate
from .scheduler import BatchScheduler, ClassSpec, WorkItem

__all__ = ["Attention", "GraphRequest", "GraphServeEngine"]

logger = logging.getLogger(__name__)

_BACKENDS = ("auto", "pallas", "windowed", "hbm", "blocked")


@dataclasses.dataclass
class GraphRequest:
    """One aggregation request: A'_graph_id @ x, answered in ORIGINAL row order."""

    graph_id: str
    x: jax.Array                       # [n_cols(graph), F]
    out: Optional[jax.Array] = None    # filled by serve()
    latency_s: Optional[float] = None  # enqueue -> answer wall time (includes
    #                                    queue wait behind earlier dispatches)
    klass: str = "default"             # SLO class (must name a ClassSpec)
    tenant: Optional[str] = None       # opaque owner tag (stats only)


@dataclasses.dataclass(frozen=True)
class Attention:
    """The attention inputs of one request, as ``submit`` validated them:
    ``el [n_cols, heads]``, ``er [n_rows, heads]`` and features of
    ``heads`` heads of ``f_head`` columns."""

    el: jax.Array
    er: jax.Array
    negative_slope: float
    heads: int
    f_head: int


class GraphServeEngine:
    """Continuous-batching multi-graph SpMM server over a partition-plan cache.

    ``submit`` is the native entry point (asynchronous, returns a
    ``Future``); ``serve``/``serve_one`` are thin synchronous wrappers that
    submit and wait, kept for backward compatibility — all three share the
    scheduler, so synchronous callers still coalesce with concurrent
    submitters.
    """

    def __init__(
        self,
        *,
        config: Optional[PartitionConfig] = None,
        cache: Optional[PlanCache] = None,
        cache_capacity: int = 32,
        backend: str = "blocked",
        max_graphs_per_batch: int = 8,
        block_bucket: Optional[int] = 8,
        max_batch_requests: Optional[int] = None,
        max_wait_ms: float = 2.0,
        max_pending: int = 256,
        feature_bucket: bool = True,
        classes: Optional[Sequence[ClassSpec]] = None,
        repair_churn_threshold: float = 0.25,
        tuner: Optional[PlanTuner] = None,
    ):
        self.config = config or PartitionConfig()
        self.cache = cache if cache is not None else PlanCache(cache_capacity)
        if backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {'|'.join(_BACKENDS)}")
        self.backend = backend
        self.max_graphs_per_batch = max_graphs_per_batch
        # min bucket tier: power-of-two tiers from here cap padding waste
        # below 2x the live blocks (the old fixed-256 floor padded a 3-block
        # batch to 256 — 85x dead grid steps).
        self.block_bucket = block_bucket
        # fused feature widths round up to powers of two: the width of a
        # same-graph group is (requests in flush) x F, which varies with
        # flush composition under concurrent traffic — bucketing keeps the
        # compiled-shape set logarithmic instead of one shape per mix
        self.feature_bucket = feature_bucket
        # above this fraction of rows dirtied by a delta, incremental plan
        # repair falls back to a full rebuild (see core.plan_repair)
        self.repair_churn_threshold = repair_churn_threshold
        self._graphs: Dict[str, CSRGraph] = {}
        self._keys: Dict[str, tuple] = {}  # graph_id -> plan key (hashed once)
        self._versions: Dict[str, int] = {}  # graph_id -> published version
        # _bind_lock guards the three maps above as ONE atomic binding:
        # readers (plan_for, _validate-time lookups) must never observe a
        # graph from version v+1 paired with the key of version v
        self._bind_lock = threading.Lock()
        # serializes mutation application + publish per engine; reads never
        # take it (they pin a version instead)
        self._mutate_lock = threading.Lock()
        # one flush absorbs several dispatches' worth of requests so a
        # deadline-triggered flush under load still fills whole batches
        self.scheduler = BatchScheduler(
            self._flush,
            max_batch=max_batch_requests or 4 * max_graphs_per_batch,
            max_wait_ms=max_wait_ms,
            max_queue=max_pending,
            name="graph-serve",
            classes=classes,
        )
        # serving counters. The base engine mutates them only on the
        # scheduler's flush thread; the fleet subclass dispatches from a
        # device pool, so every counter update takes this (uncontended in
        # the single-device case) lock.
        self._counters_lock = threading.Lock()
        self.requests_served = 0
        self.batches_dispatched = 0
        self.graphs_dispatched = 0   # distinct graphs summed over dispatches
        self.rows_served = 0
        self.values_served = 0       # rows * feature columns
        # host wall time per dispatch, from its start through
        # block_until_ready: feature prep, slab merge, upload, launch and
        # the wait on the device, summed over dispatches
        self.total_serve_s = 0.0
        # total_serve_s split by phase (spans.DISPATCH_PHASES), plus the
        # answer phase after it; exposed as dispatch_<phase>_s
        self.dispatch_phase_s: Dict[str, float] = dict.fromkeys(
            spans.DISPATCH_PHASES, 0.0)
        self.total_request_latency_s = 0.0  # sum of enqueue->answer times
        self.live_blocks = 0         # merged blocks carrying real slabs
        self.padded_blocks = 0       # blocks actually dispatched (bucketed)
        self.backend_dispatches: Dict[str, int] = {
            "resident": 0, "windowed": 0, "hbm": 0, "blocked": 0}
        self.attn_dispatches = 0     # dispatches carrying attention heads
        self.attn_heads = 0          # attention heads over those dispatches
        # row-gather passes each block of an hbm dispatch makes (F_pad / W,
        # router.hbm_gather_width), summed over hbm dispatches
        self.hbm_gather_passes = 0
        # slab rows the kernels' epilogue reads (scatter_block_rows): the
        # merged n_out + B of each dispatch
        self.epilogue_rows = 0
        self.last_decision: Optional[RoutingDecision] = None
        # mutation-path counters (versioned plan lifecycle)
        self.mutations_applied = 0   # mutate() requests resolved
        self.mutation_edges = 0      # edge inserts+deletes applied
        self.plan_repairs = 0        # publishes served by incremental repair
        self.plan_rebuilds = 0       # publishes that fell back to full build
        # --- online partition autotuning (shadow-measured rollout) -------
        # The tuner only ever acts on COPIES of live work: a shadow
        # duplicates one dispatch onto the candidate plan on a separate
        # single worker thread AFTER the live futures resolved, so the
        # serving path never waits on a candidate (reads never pay for
        # candidates). At most one shadow is in flight per engine; when
        # the worker is busy the opportunity is skipped, never queued.
        self.tuner = tuner
        self._shadow_pool: Optional[ThreadPoolExecutor] = None
        self._shadow_lock = threading.Lock()
        self._shadow_inflight = False
        # tuned dispatch hints by graph id, re-attached to plans rebuilt
        # from scratch after an eviction (the structure comes back via the
        # config in the key; the backend/grid_order hints live here)
        self._tuned_hints: Dict[str, Dict] = {}
        self.shadow_dispatches = 0   # candidate measurements completed
        self.shadow_skipped = 0      # opportunities dropped (worker busy)
        self.shadow_failures = 0     # candidate build/dispatch raised
        self.shadow_time_s = 0.0     # wall time spent in shadow dispatches
        self.tuned_promotions = 0    # tuned configs published

    # ------------------------------------------------------------------ admin
    def register_graph(self, graph_id: str, g: CSRGraph,
                       normalize: bool = False) -> PartitionPlan:
        """Register (and warm the plan for) a graph under ``graph_id``.

        Re-registering the same id with identical content is a no-op (cache
        hit); different content replaces the binding. A same-content
        re-register keeps a TUNED binding (the autotuner may have promoted
        a non-default config for this graph — identical content must not
        silently reset it to ``self.config``).
        """
        if normalize:
            g = gcn_normalize(g)
        h = graph_content_hash(g)
        with self._bind_lock:
            prev_key = self._keys.get(graph_id)
        if prev_key is not None and prev_key[0] == h and \
                prev_key != (h, self.config):
            return self.plan_for(graph_id)  # tuned binding, same content
        key = (h, self.config)
        plan = self.cache.get_by_key(
            key, lambda: build_partition_plan(g, self.config, graph_hash=h))
        with self._bind_lock:
            prev_key = self._keys.get(graph_id)
            prev_ver = self._versions.get(graph_id)
            if prev_key == plan.key and prev_ver is not None:
                version = prev_ver          # idempotent re-register
            elif prev_ver is not None:
                # content replacement continues the id's version chain so
                # directory/version invalidation stays monotone
                version = max(plan.version, prev_ver + 1)
            else:
                version = plan.version
            self._graphs[graph_id] = g
            self._keys[graph_id] = plan.key
            self._versions[graph_id] = version
        return plan

    def register_subgraph(self, g: CSRGraph, prefix: str = "sub",
                          normalize: bool = False) -> str:
        """Register an induced subgraph under a CONTENT-DERIVED id.

        The id is ``f"{prefix}:{content_hash[:16]}"`` — the same frontier
        sampled twice registers under the same id and partitions exactly
        once (registration is idempotent on identical content). This is
        the submission path for sampled-inference frontiers: callers never
        invent ids, so recurring frontiers from different callers share
        one plan-cache entry. Returns the graph id to pass to ``submit``.
        """
        if normalize:
            g = gcn_normalize(g)
        graph_id = f"{prefix}:{graph_content_hash(g)[:16]}"
        self.register_graph(graph_id, g)
        return graph_id

    def unregister_graph(self, graph_id: str) -> bool:
        """Drop a graph's binding (id -> graph/key/version/tuned hints).

        The plan itself stays in the LRU cache until evicted — a later
        ``register_subgraph`` of the same content re-binds without a
        rebuild. The caller must have drained in-flight work for the id
        (the sampling service evicts only after results are gathered);
        the engine does not fence racing submits. Returns whether the id
        was registered.
        """
        with self._bind_lock:
            known = graph_id in self._graphs
            self._graphs.pop(graph_id, None)
            self._keys.pop(graph_id, None)
            self._versions.pop(graph_id, None)
            self._tuned_hints.pop(graph_id, None)
        return known

    def submit_gather(self, graph_id: str, x: jax.Array,
                      rows: np.ndarray, *, block: bool = True,
                      klass: str = "default",
                      tenant: Optional[str] = None) -> Future:
        """``submit`` plus a gather epilogue: the returned ``Future``
        resolves to ``aggregation[rows]`` instead of the full ``[n_rows,
        F]`` output. This is how sampled inference extracts per-seed
        outputs from a frontier subgraph dispatch without shipping the
        whole frontier's activations back to the caller.
        """
        rows = np.asarray(rows, dtype=np.int64)
        inner = self.submit(graph_id, x, block=block, klass=klass,
                            tenant=tenant)
        outer: Future = Future()

        def _chain(f: Future) -> None:
            if f.cancelled():
                outer.cancel()
                return
            exc = f.exception()
            if exc is not None:
                outer.set_exception(exc)
                return
            try:
                outer.set_result(f.result()[rows])
            except BaseException as e:  # noqa: BLE001 — surfaced via future
                outer.set_exception(e)

        inner.add_done_callback(_chain)
        return outer

    def graph_ids(self) -> List[str]:
        with self._bind_lock:
            return list(self._graphs)

    def plan_for(self, graph_id: str) -> PartitionPlan:
        """Resolve a registered graph's plan WITHOUT rehashing its arrays —
        the content hash was paid once at registration; a rebuild only
        happens if the plan was LRU-evicted since. The rebuild uses the
        config EMBEDDED IN THE KEY (not ``self.config``): after the tuner
        promotes a non-default config, an evicted plan must rebuild with
        its tuned structure. Tuned dispatch hints are re-attached from the
        engine's hint map when the rebuild lost them."""
        with self._bind_lock:   # key and graph must be the SAME version
            key = self._keys[graph_id]
            g = self._graphs[graph_id]
        plan = self.cache.get_by_key(
            key, lambda: build_partition_plan(
                g, key[1], graph_hash=key[0]))
        if plan.tuned is None:
            hints = self._tuned_hints.get(graph_id)
            if hints is not None and plan.key[1] == hints["config"]:
                plan.tuned = hints["tuned"]
        return plan

    def graph_version(self, graph_id: str) -> int:
        """Current published version of a registered graph's plan chain."""
        with self._bind_lock:
            return self._versions[graph_id]

    def close(self) -> None:
        """Stop the background scheduler (drains anything still queued)."""
        self.scheduler.stop()
        with self._shadow_lock:
            pool, self._shadow_pool = self._shadow_pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    # ------------------------------------------------------------------ serve
    def _registered(self, graph_id: str) -> CSRGraph:
        with self._bind_lock:
            g = self._graphs.get(graph_id)
            if g is None:
                raise KeyError(f"graph {graph_id!r} not registered "
                               f"(known: {sorted(self._graphs)})")
        return g

    def _validate(self, graph_id: str, x) -> None:
        """Cheap synchronous admission checks: registration + feature shape.

        Deliberately does NOT touch the plan cache — the registered graph
        already knows its n_cols, so validation stays O(1) on the caller
        thread and plan resolution (which can mean an O(n) rebuild after an
        eviction) happens on the flush thread where it belongs.
        """
        g = self._registered(graph_id)
        shape = tuple(getattr(x, "shape", ()))
        if len(shape) != 2 or shape[0] != g.n_cols:
            raise ValueError(
                f"request for {graph_id!r} has features {shape}, "
                f"expected [{g.n_cols}, F]")

    def _validate_attention(self, graph_id: str, x, attention,
                            negative_slope: float) -> Attention:
        """Admission checks of an attention request: features ``[n_cols,
        H, F_h]``, ``el [n_cols, H]``, ``er [n_rows, H]`` and a finite
        negative slope."""
        g = self._registered(graph_id)
        shape = tuple(getattr(x, "shape", ()))
        if len(shape) != 3:
            raise ValueError(
                f"attention request for {graph_id!r} has features {shape}, "
                f"expected [{g.n_cols}, H, F_h]")
        n_heads, f_head = shape[1], shape[2]
        try:
            el, er = attention
        except (TypeError, ValueError):
            raise ValueError("attention must be a pair (el, er)") from None
        want = {"features": ((g.n_cols,), shape[:1]),
                "el": ((g.n_cols, n_heads), tuple(getattr(el, "shape", ()))),
                "er": ((g.n_rows, n_heads), tuple(getattr(er, "shape", ())))}
        for name, (expected, got) in want.items():
            if got != expected:
                raise ValueError(f"attention request for {graph_id!r}: "
                                 f"{name} shape {got}, expected {expected}")
        if not math.isfinite(negative_slope):
            raise ValueError(f"negative_slope must be finite, got "
                             f"{negative_slope!r}")
        return Attention(el, er, float(negative_slope), n_heads, f_head)

    def submit(self, graph_id: str, x: jax.Array, *,
               attention: Optional[Tuple[jax.Array, jax.Array]] = None,
               negative_slope: float = 0.2,
               block: bool = True, klass: str = "default",
               tenant: Optional[str] = None) -> Future:
        """Admit one request; returns a ``Future`` of the ``[n_rows, F]``
        aggregation in ORIGINAL row order.

        With ``attention=(el, er)`` the request is a graph attention
        aggregation over ``H`` heads: ``x`` is ``z [n_cols, H, F_h]``,
        ``el [n_cols, H]`` and
        ``er [n_rows, H]``, and the answer, shaped like ``z``, is ``out[i,
        h] = sum_j softmax_j(LeakyReLU(el[j, h] + er[i, h])) z[j, h]`` over
        row i's nonzeros j, the LeakyReLU with ``negative_slope``.

        Validation (unknown graph, wrong feature or score shape, unknown
        SLO class, an engine that serves no attention) raises here,
        synchronously. A full admission queue blocks (backpressure) or,
        with ``block=False``, raises
        :class:`repro.serve.scheduler.QueueFullError`. ``klass`` names one
        of the engine's configured :class:`ClassSpec` entries; ``tenant``
        is an opaque owner tag carried into per-class stats.
        """
        if attention is None:
            self._validate(graph_id, x)
            payload = (graph_id, x)
        else:
            payload = (graph_id, x, self._validate_attention(
                graph_id, x, attention, negative_slope))
        return self.scheduler.submit(payload, block=block,
                                     klass=klass, tenant=tenant).future

    def mutate(self, graph_id: str, delta: EdgeDelta, *,
               block: bool = True, klass: str = "default",
               tenant: Optional[str] = None) -> Future:
        """Admit a batched edge delta against a registered graph.

        Returns a ``Future`` resolving to a dict
        ``{"graph_id", "version", "repaired", "reason", "dirty_rows"}``
        once the new plan version is PUBLISHED — later submits observe the
        mutated graph. Mutations ride the same admission queue as reads:
        a flush dispatches its reads first (against the pre-publish
        version, which they pin for the duration of the kernel call), then
        applies that flush's deltas per graph in arrival order and
        publishes once per graph. In-flight reads are therefore never
        blocked and never torn — every answer is consistent with either
        the pre- or post-publish version.
        """
        with self._bind_lock:
            if graph_id not in self._graphs:
                raise KeyError(f"graph {graph_id!r} not registered "
                               f"(known: {sorted(self._graphs)})")
        if not isinstance(delta, EdgeDelta):
            raise TypeError(f"delta must be an EdgeDelta, got {type(delta)!r}")
        return self.scheduler.submit((graph_id, delta, "mutate"), block=block,
                                     klass=klass, tenant=tenant).future

    def serve_one(self, graph_id: str, x: jax.Array) -> jax.Array:
        """Convenience single-request path (still goes through the batch code)."""
        return self.serve([GraphRequest(graph_id, x)])[0].out

    def serve(self, requests: Sequence[GraphRequest]) -> List[GraphRequest]:
        """Synchronous wrapper: submit every request and wait for all answers.

        Validates EVERY request before admitting ANY, so a malformed
        request cannot leave the call half-served with mutated counters.
        The requests enter the admission queue as one contiguous run and
        typically share flushes (and fused dispatches) — including with
        requests other threads submitted concurrently.
        """
        for r in requests:
            self._validate(r.graph_id, r.x)
        items = self.scheduler.submit_many(
            [(r.graph_id, r.x) for r in requests],
            klass=[r.klass for r in requests],
            tenant=[r.tenant for r in requests])
        first_exc: Optional[BaseException] = None
        for r, item in zip(requests, items):
            try:
                r.out = item.future.result()
                r.latency_s = item.latency_s
            except BaseException as e:  # noqa: BLE001 — re-raised below
                if first_exc is None:
                    first_exc = e
        if first_exc is not None:
            raise first_exc
        return list(requests)

    # ------------------------------------------------------------------ flush
    @staticmethod
    def _group_by_graph(items: List[WorkItem]
                        ) -> Tuple[List[str], Dict[str, List[WorkItem]]]:
        """Group a flush's items by graph id, in order of first appearance
        (shared with the fleet engines' flushes). Payloads are
        ``(graph_id, x, ...)`` — extra elements (the multihost engine's
        pinned-local marker) ride along untouched."""
        order: List[str] = []
        groups: Dict[str, List[WorkItem]] = {}
        for item in items:
            gid = item.payload[0]
            if gid not in groups:
                groups[gid] = []
                order.append(gid)
            groups[gid].append(item)
        return order, groups

    @staticmethod
    def _slice_answers(grp: List[WorkItem], widths: List[int],
                       out: jax.Array, now: float
                       ) -> Tuple[List[Tuple[WorkItem, jax.Array]], float]:
        """Split a fused group's output back per request: feature columns
        sliced by each item's width (``_column_cuts``, as the local
        dispatch cuts them), plus the summed enqueue->now wait. Used by
        the fleet's sharded and forwarded dispatch paths."""
        answers = [(item, cut(out))
                   for item, cut in GraphServeEngine._column_cuts(grp, widths)]
        return answers, sum(now - item.t_enqueue for item in grp)

    @staticmethod
    def _is_mutation(item: WorkItem) -> bool:
        """Mutation payloads are ``(graph_id, EdgeDelta, "mutate")`` — the
        marker is in slot 2 so read payloads (and the multihost engine's
        ``"pinned-local"`` marker) are never mistaken for deltas."""
        p = item.payload
        return len(p) > 2 and p[2] == "mutate"

    def _flush(self, items: List[WorkItem]) -> None:
        """Scheduler flush callback: reads first, then mutations.

        Reads dispatch against the flush's pre-publish plan versions;
        mutations for the same graph coalesce and publish ONCE at the end
        of the flush, so a mutate never blocks the reads it arrived with.
        A failing read dispatch still lets this flush's mutations publish
        (and vice versa a bad delta fails only its own graph's mutation
        items, never the reads).
        """
        reads = [it for it in items if not self._is_mutation(it)]
        mutations = [it for it in items if self._is_mutation(it)]
        read_exc: Optional[BaseException] = None
        if reads:
            try:
                self._flush_reads(reads)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                read_exc = e
        if mutations:
            order, groups = self._group_by_graph(mutations)
            for gid in order:
                grp = groups[gid]
                try:
                    self._apply_mutation(gid, grp)
                except BaseException as e:  # noqa: BLE001 — isolate per graph
                    for it in grp:
                        it.fail(e)
        if read_exc is not None:
            raise read_exc

    def _flush_reads(self, items: List[WorkItem]) -> None:
        """Group reads by plan, fuse, dispatch in chunks.

        Runs on the scheduler thread. Requests naming the same graph fuse
        along the feature axis (one slab gather serves all of them);
        distinct graphs chunk into fused dispatches of up to
        ``max_graphs_per_batch`` in order of first appearance. Every plan
        used is version-pinned for the duration of its dispatches: a
        concurrent publish retires the old version but cannot reclaim it
        until the last in-flight dispatch unpins.
        """
        order, groups = self._group_by_graph(items)
        plans = {gid: self.plan_for(gid) for gid in order}
        pinned = [p.key for p in plans.values()]
        for k in pinned:
            self.cache.pin_version(k)
        try:
            # a raising dispatch aborts the remaining chunks: their items
            # are failed by the scheduler with the same exception, while
            # items of already-dispatched chunks keep their results
            for start in range(0, len(order), self.max_graphs_per_batch):
                chunk = order[start:start + self.max_graphs_per_batch]
                self._dispatch(
                    [(gid, groups[gid], plans[gid]) for gid in chunk])
        finally:
            for k in pinned:
                self.cache.unpin_version(k)

    # --------------------------------------------------------------- mutation
    def _apply_mutation(self, gid: str, grp: List[WorkItem]) -> None:
        """Apply one flush's coalesced deltas for ``gid`` and publish once.

        Deltas apply SEQUENTIALLY in arrival order (never merged: a delete
        in delta k must see the graph as delta k-1 left it), the plan is
        repaired once against the combined touched-row set, and the new
        version publishes atomically — the old version is retired and
        reclaimed when its last pinned reader drains.
        """
        with self._mutate_lock:
            with self._bind_lock:
                g_old = self._graphs[gid]
                old_key = self._keys[gid]
                cur_ver = self._versions[gid]
            plan_old = self.plan_for(gid)
            g_new = g_old
            touched: List[np.ndarray] = []
            n_edges = 0
            gh = plan_old.graph_hash
            for it in grp:
                delta: EdgeDelta = it.payload[1]
                g_new = delta.apply(g_new)
                touched.append(delta.touched_rows())
                n_edges += delta.size
                gh = delta_chain_hash(gh, delta)
            pv = repair_plan(
                plan_old, g_old, g_new,
                np.unique(np.concatenate(touched)) if touched
                else np.empty(0, np.int64),
                churn_threshold=self.repair_churn_threshold,
                graph_hash=gh)
            # the engine owns the id's version CHAIN; the repair stamp is
            # relative to the plan object, which may have been rebuilt (at
            # version 0) after an eviction
            pv.version = cur_ver + 1
            pv.plan.version = cur_ver + 1
            self._publish_version(gid, g_new, pv.plan, old_key)
            with self._counters_lock:
                self.mutations_applied += len(grp)
                self.mutation_edges += n_edges
                if pv.repaired:
                    self.plan_repairs += 1
                else:
                    self.plan_rebuilds += 1
        result = {"graph_id": gid, "version": pv.version,
                  "repaired": pv.repaired, "reason": pv.reason,
                  "dirty_rows": pv.dirty_rows}
        for it in grp:
            it.complete(dict(result))

    def _publish_version(self, gid: str, g_new: CSRGraph,
                         plan: PartitionPlan, old_key: tuple) -> None:
        """Publish hook: cache publish first (so plan_for never misses),
        THEN atomically re-bind the id. Subclasses extend this to also
        record the version in the placement directory / notify peers."""
        self.cache.publish(plan, retire_key=old_key)
        with self._bind_lock:
            self._graphs[gid] = g_new
            self._keys[gid] = plan.key
            self._versions[gid] = plan.version

    @spans.span(spans.DISPATCH)
    def _dispatch(self, batch: List[Tuple[str, List[WorkItem],
                                          PartitionPlan]]) -> None:
        """One fused kernel call over up to max_graphs_per_batch graphs,
        traced as the ``gcn.dispatch`` span and its phases
        (:mod:`repro.core.spans`), each phase timed into the engine's
        ``dispatch_<phase>_s`` counter."""
        t0 = time.perf_counter()
        phases: Dict[str, float] = {}
        plans = [plan for _gid, _grp, plan in batch]
        heads: Optional[List[HeadValues]] = None
        with spans.span(spans.PREPARE, phases):
            if any(self._attention_of(it) for _gid, grp, _plan in batch
                   for it in grp):
                xs, heads, cuts = self._prepare_heads(batch)
            else:
                xs, cuts = self._prepare_plain(batch)

        b_total = sum(p.num_blocks for p in plans)
        pad_to = None
        if self.block_bucket:
            pad_to = bucket_blocks(b_total, self.block_bucket)
        backend, grid_order = self._effective_launch(plans)
        slabs = [p.slabs if heads is None
                 else dict(p.slabs, nnz=p.partition.nnz_blk) for p in plans]
        outs, decision = spmm_batched(
            slabs, xs, [p.n_rows for p in plans],
            backend=backend, pad_blocks_to=pad_to, return_decision=True,
            grid_order=grid_order, phases=phases, heads=heads)
        with spans.span(spans.WAIT, phases):
            jax.block_until_ready(outs)
        # host wall time of this dispatch up to ready outputs: the phases
        # prepare, merge, upload, scores, launch and wait, back to back
        dt = time.perf_counter() - t0

        executed = decision.backend if decision is not None else "blocked"
        with self._counters_lock:
            self.backend_dispatches[executed] += 1
            if executed == "hbm":
                self.hbm_gather_passes += (decision.f_pad
                                           // decision.gather_width)
            self.last_decision = decision
            self.epilogue_rows += (sum(p.n_rows for p in plans)
                                   + (pad_to or b_total))
            self.live_blocks += b_total
            self.padded_blocks += pad_to if pad_to else b_total
            if heads is not None:
                self.attn_dispatches += 1
                self.attn_heads += sum(int(np.sum(h.kind == ATTENTION))
                                       for h in heads)
        if logger.isEnabledFor(logging.DEBUG):
            logger.debug(
                "dispatch: graphs=%d blocks=%d->%d backend=%s (%s) %.1fms",
                len(batch), b_total, pad_to or b_total, executed,
                decision.reason if decision else "jnp twin", dt * 1e3)

        # update every counter BEFORE resolving any future: a synchronous
        # caller unblocks the moment its future resolves and may read
        # stats() immediately
        now = time.perf_counter()
        answers: List[Tuple[WorkItem, jax.Array]] = []
        n_req = n_rows = n_vals = 0
        wait_s = 0.0
        with spans.span(spans.ANSWER, phases):
            for (_gid, grp, plan), out, cut in zip(batch, outs, cuts):
                out = out[plan.inv_perm]      # back to original row order
                for item, answer in cut:
                    result = answer(out)
                    answers.append((item, result))
                    n_vals += math.prod(result.shape)
                    wait_s += now - item.t_enqueue
                n_req += len(grp)
                n_rows += plan.n_rows * len(grp)
        # only the increments sit under the lock (concurrent fleet device
        # launches must not serialize their un-permute/slice work on it)
        with self._counters_lock:
            self.requests_served += n_req
            self.rows_served += n_rows
            self.values_served += n_vals
            self.total_request_latency_s += wait_s
            self.batches_dispatched += 1
            self.graphs_dispatched += len(batch)
            self.total_serve_s += dt
            for name, seconds in phases.items():
                self.dispatch_phase_s[name] += seconds
        for item, result in answers:
            item.complete(result)
        # autotuning LAST: every live answer above already resolved, so
        # shadow work can never sit between a request and its result
        if self.tuner is not None and heads is None:
            self._tuner_tick(batch, xs, dt)

    @staticmethod
    def _attention_of(item: WorkItem) -> Optional[Attention]:
        p = item.payload
        return p[2] if len(p) > 2 and isinstance(p[2], Attention) else None

    def _prepare_plain(self, batch):
        """Features of a dispatch of plain requests: each graph's requests
        side by side, the width bucketed; per item, the cut of its answer
        from the graph's (original row order)."""
        xs, cuts = [], []
        for _gid, grp, _plan in batch:
            feats = [jnp.asarray(it.payload[1], dtype=jnp.float32)
                     for it in grp]
            x = (feats[0] if len(feats) == 1
                 else jnp.concatenate(feats, axis=1))
            if self.feature_bucket:
                w = int(x.shape[1])
                pad = bucket_blocks(w, 1) - w   # next power of two
                if pad:
                    x = jnp.pad(x, ((0, 0), (0, pad)))
            xs.append(x)
            cuts.append(self._column_cuts(
                grp, [int(f.shape[1]) for f in feats]))
        return xs, cuts

    @staticmethod
    def _column_cuts(items, widths, col: int = 0):
        """The cuts of requests whose features, ``widths`` wide, sit side
        by side from column ``col`` of their graph's answer."""
        cut = []
        for it, w in zip(items, widths):
            cut.append((it, lambda out, c=col, w=w: out[:, c:c + w]))
            col += w
        return cut

    def _prepare_heads(self, batch):
        """Features of a dispatch that carries attention requests: every
        graph's become H heads of ``fhp`` columns, H the most heads a graph
        of the dispatch needs and ``fhp`` its widest head padded to whole
        128-lane tiles. A graph's heads are its attention requests' heads in
        arrival order, then one head of its plain requests side by side
        (the plan's own values), then zero heads. Returns the features, the
        :class:`HeadValues` and the cuts of ``_prepare_plain``."""
        split = [([it for it in grp if self._attention_of(it)],
                  [it for it in grp if not self._attention_of(it)])
                 for _gid, grp, _plan in batch]
        widths = [self._attention_of(it).f_head
                  for att, _ in split for it in att]
        widths += [sum(int(it.payload[1].shape[1]) for it in plain)
                   for _, plain in split if plain]
        fhp = max(pad_features(w, 128) for w in widths)
        H = max(sum(self._attention_of(it).heads for it in att) + bool(plain)
                for att, plain in split)
        xs, heads, cuts = [], [], []
        for (_gid, _grp, plan), (att, plain) in zip(batch, split):
            n = plan.n_cols
            cols, els, ers, kind, slope, cut = [], [], [], [], [], []
            for it in att:
                a = self._attention_of(it)
                z = jnp.asarray(it.payload[1], jnp.float32)
                cols.append(jnp.pad(z, ((0, 0), (0, 0), (0, fhp - a.f_head))
                                    ).reshape(n, a.heads * fhp))
                els.append(jnp.asarray(a.el, jnp.float32))
                ers.append(jnp.asarray(a.er, jnp.float32))
                cut.append((it, self._head_cut(len(kind), a, H, fhp)))
                kind += [ATTENTION] * a.heads
                slope += [a.negative_slope] * a.heads
            rest = H - len(kind)           # heads with no scores
            if plain:
                feats = [jnp.asarray(it.payload[1], jnp.float32)
                         for it in plain]
                x = jnp.concatenate(feats, axis=1)
                cols.append(jnp.pad(x, ((0, 0), (0, fhp - x.shape[1]))))
                cut += self._column_cuts(
                    plain, [int(f.shape[1]) for f in feats],
                    len(kind) * fhp)
                kind.append(PLAIN)
            if len(kind) < H:
                cols.append(jnp.zeros((n, (H - len(kind)) * fhp),
                                      jnp.float32))
                kind += [NONE] * (H - len(kind))
            slope += [0.0] * rest
            els.append(jnp.zeros((n, rest), jnp.float32))
            ers.append(jnp.zeros((plan.n_rows, rest), jnp.float32))
            xs.append(jnp.concatenate(cols, axis=1))
            heads.append(HeadValues(
                np.asarray(kind, np.int32), np.asarray(slope, np.float32),
                jnp.concatenate(els, axis=1),
                to_plan_order(jnp.concatenate(ers, axis=1), plan.inv_perm)))
            cuts.append(cut)
        return xs, heads, cuts

    @staticmethod
    def _head_cut(h0: int, a: Attention, H: int, fhp: int):
        """The cut of one attention request's answer: its heads, unpadded,
        ``[n_rows, heads, f_head]``."""
        return lambda out: out.reshape(out.shape[0], H, fhp)[
            :, h0:h0 + a.heads, :a.f_head]

    # ------------------------------------------------------------ autotuning
    def _effective_launch(self, plans: List[PartitionPlan]
                          ) -> Tuple[str, str]:
        """Backend/grid_order for one fused dispatch: a plan's tuned hints
        apply when every plan in the batch agrees on the effective pair
        (trivially true for the single-graph dispatches that dominate hot
        traffic); a mixed batch falls back to the engine defaults."""
        pairs = {(((p.tuned or {}).get("backend")) or self.backend,
                  ((p.tuned or {}).get("grid_order")) or "block_major")
                 for p in plans}
        if len(pairs) == 1:
            return pairs.pop()
        return self.backend, "block_major"

    def _tuner_tick(self, batch, xs, dt: float) -> None:
        """Per-dispatch tuner hook (runs AFTER the live futures resolved).

        Feeds the rate tracker, asks the tuner whether any graph in this
        batch is due a shadow measurement, and hands at most one shadow to
        the single worker thread. Multihost engines skip shadowing —
        promotion would re-key the plan under the directory's feet; only
        single-host engines tune (the multihost follow-on needs a version
        broadcast like mutate()'s).
        """
        for gid, grp, _ in batch:
            self.tuner.observe(gid, len(grp))
        if getattr(self, "directory", None) is not None:
            return      # multihost: directory-owned keys don't tune yet
        for (gid, _grp, plan), x in zip(batch, xs):
            cand = self.tuner.next_shadow(gid, plan.config)
            if cand is None:
                continue
            self._submit_shadow(gid, plan, cand, x)

    def _submit_shadow(self, gid: str, plan_i: PartitionPlan,
                       cand: TuningCandidate, x: jax.Array) -> None:
        """Hand one shadow measurement to the worker; skip if it's busy
        (shadows are opportunistic — never queued, never blocking)."""
        with self._shadow_lock:
            if self._shadow_inflight:
                busy = True
            else:
                busy = False
                self._shadow_inflight = True
                if self._shadow_pool is None:
                    self._shadow_pool = ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix="plan-shadow")
                pool = self._shadow_pool
        if busy:
            with self._counters_lock:
                self.shadow_skipped += 1
            return
        pool.submit(self._run_shadow, gid, plan_i, cand, x)

    def _run_shadow(self, gid: str, plan_i: PartitionPlan,
                    cand: TuningCandidate, x: jax.Array) -> None:
        """Worker-thread body: build the candidate plan (single-flight via
        the cache) and run a PAIRED A/B measurement — the incumbent and
        candidate plans dispatch the SAME features back-to-back in this
        thread (1 untimed candidate warmup to absorb compilation, then
        timed runs in ABBA order with the per-side min scored). Pairing is
        what makes the comparison robust: both sides see the same
        background load, so scheduler/GIL contention cancels instead of
        poisoning the candidate's numbers. A promotion signal publishes
        the candidate through the version chain."""
        t_start = time.perf_counter()
        old_key = plan_i.key
        try:
            with self._bind_lock:
                stale = self._keys.get(gid) != old_key
                g = self._graphs.get(gid)
            if stale or g is None:
                return              # graph mutated/replaced since the tick
            key = (old_key[0], cand.config)
            plan_c = self.cache.get_by_key(
                key, lambda: build_partition_plan(
                    g, cand.config, graph_hash=old_key[0]))
            hints_i = plan_i.tuned or {}
            launches = {
                "inc": (plan_i, hints_i.get("backend") or self.backend,
                        hints_i.get("grid_order") or "block_major"),
                "cand": (plan_c, cand.backend or self.backend,
                         cand.grid_order),
            }

            def _once(which: str) -> float:
                plan, backend, grid_order = launches[which]
                pad_to = (bucket_blocks(plan.num_blocks, self.block_bucket)
                          if self.block_bucket else None)
                t0 = time.perf_counter()
                jax.block_until_ready(spmm_batched(
                    [plan.slabs], [x], [plan.n_rows],
                    backend=backend, pad_blocks_to=pad_to,
                    grid_order=grid_order))
                return time.perf_counter() - t0

            _once("cand")           # warmup: compilation must not score
            # ABBA order de-phases background load: a live dispatch that
            # overlaps the shadow window hits early and late slots alike,
            # so neither side's min is systematically the contended one
            # (short candidate runs otherwise phase-lock into the busy
            # slots while long incumbent runs land in the idle gaps).
            samples = [(w, _once(w)) for w in ("inc", "cand", "cand", "inc")]
            incumbent_s = min(s for w, s in samples if w == "inc")
            candidate_s = min(s for w, s in samples if w == "cand")
            with self._counters_lock:
                self.shadow_dispatches += 1
            winner = self.tuner.record_shadow(gid, cand, incumbent_s,
                                              candidate_s)
            if winner is not None:
                self._promote_tuned(gid, old_key, winner, plan_c)
        except Exception:  # noqa: BLE001 — a broken candidate must not
            logger.exception("shadow measurement failed for %r (%s)",
                             gid, cand.label)        # take down the worker
            with self._counters_lock:
                self.shadow_failures += 1
            self.tuner.candidate_failed(gid, cand)
        finally:
            with self._counters_lock:
                self.shadow_time_s += time.perf_counter() - t_start
            with self._shadow_lock:
                self._shadow_inflight = False

    def _promote_tuned(self, gid: str, old_key: tuple,
                       cand: TuningCandidate, plan_c: PartitionPlan) -> None:
        """Publish a winning candidate as the graph's next plan version.

        Rides the same machinery as mutate(): under the mutation lock the
        binding is re-checked (a racing mutation aborts the promotion —
        the tuner forgets the graph and re-tunes if it stays hot), the
        plan gets its tuned hints + the next chain version, and
        ``_publish_version`` atomically publishes + re-binds. In-flight
        reads keep their pinned incumbent version until they drain.
        """
        with self._mutate_lock:
            with self._bind_lock:
                if self._keys.get(gid) != old_key:
                    aborted = True
                else:
                    aborted = False
                    cur_ver = self._versions[gid]
                    g = self._graphs[gid]
            if aborted:
                self.tuner.reset(gid)
                return
            plan_c.tuned = cand.tuned_hints()
            plan_c.version = cur_ver + 1
            self._publish_version(gid, g, plan_c, old_key)
            self._tuned_hints[gid] = {"config": cand.config,
                                      "tuned": dict(plan_c.tuned)}
            with self._counters_lock:
                self.tuned_promotions += 1
        self.tuner.confirm_promoted(gid)
        logger.info("promoted tuned config for %r: %s (version %d)",
                    gid, cand.label, plan_c.version)

    # ------------------------------------------------------------------ stats
    def stats(self) -> Dict[str, float]:
        s = {f"cache_{k}": v for k, v in self.cache.stats().items()}
        s.update({f"sched_{k}": v
                  for k, v in self.scheduler.stats().items()})
        if self.tuner is not None:
            s.update({f"tuner_{k}": v
                      for k, v in self.tuner.stats().items()})
        # engine counters are one atomic snapshot (same guarantee as
        # PlanCache.stats()); cache/scheduler snapshots above are each
        # internally consistent but taken a moment earlier
        with self._counters_lock:
            return self._stats_locked(s)

    def _stats_locked(self, s: Dict[str, float]) -> Dict[str, float]:
        s.update(
            registered_graphs=len(self._graphs),
            requests_served=self.requests_served,
            batches_dispatched=self.batches_dispatched,
            rows_served=self.rows_served,
            values_served=self.values_served,
            total_serve_s=self.total_serve_s,
            # total_serve_s by phase (prepare, merge, upload, launch, wait
            # add up to it within timer overhead), then the answer phase
            **{f"dispatch_{name.rsplit('.', 1)[1]}_s": seconds
               for name, seconds in self.dispatch_phase_s.items()},
            requests_per_batch=(self.requests_served / self.batches_dispatched
                                if self.batches_dispatched else 0.0),
            # cross-caller coalescing: >1 means fused multi-graph dispatches
            graphs_per_dispatch=(self.graphs_dispatched
                                 / self.batches_dispatched
                                 if self.batches_dispatched else 0.0),
            rows_per_s=(self.rows_served / self.total_serve_s
                        if self.total_serve_s else 0.0),
            # routing: which kernel regime each fused dispatch executed on
            routed_resident=self.backend_dispatches["resident"],
            routed_windowed=self.backend_dispatches["windowed"],
            routed_hbm=self.backend_dispatches["hbm"],
            hbm_gather_passes=self.hbm_gather_passes,
            epilogue_rows=self.epilogue_rows,
            routed_blocked=self.backend_dispatches["blocked"],
            # graph attention: dispatches with per-call values, their heads
            attn_dispatches=self.attn_dispatches,
            attn_heads=self.attn_heads,
            # block bucketing waste: padded/live == 1.0 means no dead steps
            live_blocks=self.live_blocks,
            padded_blocks=self.padded_blocks,
            block_pad_ratio=(self.padded_blocks / self.live_blocks
                             if self.live_blocks else 0.0),
            # latency: per-dispatch host wall time vs per-request wait
            avg_dispatch_s=(self.total_serve_s / self.batches_dispatched
                            if self.batches_dispatched else 0.0),
            avg_request_latency_s=(
                self.total_request_latency_s / self.requests_served
                if self.requests_served else 0.0),
            # versioned plan lifecycle: streaming mutations
            mutations_applied=self.mutations_applied,
            mutation_edges=self.mutation_edges,
            plan_repairs=self.plan_repairs,
            plan_rebuilds=self.plan_rebuilds,
            # online autotuning: shadow measurements + promotions
            shadow_dispatches=self.shadow_dispatches,
            shadow_skipped=self.shadow_skipped,
            shadow_failures=self.shadow_failures,
            shadow_time_s=self.shadow_time_s,
            tuned_promotions=self.tuned_promotions,
            tuned_graphs=len(self._tuned_hints),
        )
        return s

