"""Fleet serving: sharded SpMM dispatch, plan placement, FleetGraphEngine.

These tests adapt to the visible device count: under the plain suite (one
CPU device) every code path still executes through a degenerate 1-device
mesh; under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
matrix entry) the same tests exercise real multi-device semantics. The
subprocess test at the bottom guarantees 8-device coverage even in a plain
local run.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.graph import gcn_normalize
from repro.core.plan_cache import PartitionConfig, build_partition_plan
from repro.data.graphs import make_power_law_graph
from repro.distributed import (
    ConsistentHashRing, FleetPlanCache, round_robin_block_order,
    spmm_block_sharded, spmm_feature_sharded,
)
from repro.kernels.ops import spmm_blocked
from repro.kernels.router import route_fleet
from repro.launch.mesh import graph_mesh
from repro.serve.fleet import FleetGraphEngine
from repro.serve.graph_engine import GraphRequest, GraphServeEngine

from graph_helpers import make_powerlaw_csr


def _plan(n=400, e=2600, seed=0):
    g = gcn_normalize(make_power_law_graph(n, e, seed=seed))
    return g, build_partition_plan(g, PartitionConfig())


# --------------------------------------------------------------- placement
@settings(max_examples=60)
@given(num_blocks=st.integers(min_value=0, max_value=500),
       n_devices=st.integers(min_value=1, max_value=16))
def test_round_robin_balanced_within_one_block(num_blocks, n_devices):
    """Property (acceptance): ANY plan's blocks round-robin onto d devices
    land balanced within 1 block, and every block is placed exactly once."""
    order, live = round_robin_block_order(num_blocks, n_devices)
    assert live.sum() == num_blocks
    assert live.max() - live.min() <= 1
    per = len(order) // n_devices
    assert len(order) % n_devices == 0
    # device-major layout: device k's slice holds exactly the blocks
    # congruent to k mod d, in original (degree-sorted) order
    for k in range(n_devices):
        mine = order[k * per:(k + 1) * per]
        live_mine = mine[mine < num_blocks]
        assert np.all(live_mine % n_devices == k)
        assert np.all(np.diff(live_mine) > 0)


def test_consistent_hash_ring_deterministic_and_covering():
    ring = ConsistentHashRing(range(8), vnodes=64)
    keys = [f"graph-{i}" for i in range(400)]
    owners = [ring.lookup(k) for k in keys]
    assert owners == [ring.lookup(k) for k in keys], "lookup must be stable"
    assert set(owners) == set(range(8)), "400 keys should touch all 8 arcs"
    # a second ring with the same members agrees (cross-process placement)
    again = ConsistentHashRing(range(8), vnodes=64)
    assert owners == [again.lookup(k) for k in keys]


def test_fleet_cache_places_each_plan_on_exactly_one_device():
    cache = FleetPlanCache(jax.devices(), capacity_per_device=8)
    cfg = PartitionConfig()
    keys = []
    for i in range(6):
        g = gcn_normalize(make_powerlaw_csr(n=80 + 17 * i, seed=i))
        plan = cache.get_or_build(g, cfg)
        keys.append(plan.key)
        dev_idx = cache.device_index_of(plan.key)
        assert plan.slabs["colidx"].devices() == {cache.devices[dev_idx]}, \
            "plan must be staged on its owning device"
    # resident on exactly one shard, placement sticky across lookups
    for key in keys:
        assert sum(key in s for s in cache.shards) == 1
        assert cache.device_index_of(key) == cache.device_index_of(key)
    st_ = cache.stats()
    assert st_["builds"] == 6 and st_["size"] == 6
    assert sum(st_["shard_sizes"]) == 6


def test_fleet_cache_load_aware_override():
    """When the ring's pick is far fuller than the emptiest shard, the plan
    goes to the least-loaded shard instead (and the placement sticks)."""
    # a 2-shard fleet on one physical device: the placement policy is pure
    # bookkeeping and does not need 2 real devices
    dev = jax.devices()[0]
    cache = FleetPlanCache([dev, dev], capacity_per_device=64, load_spread=2)
    cfg = PartitionConfig()
    # stuff the ring's favorite shard well past the spread
    target = 0
    for i in range(cache.load_spread + 2):
        g = gcn_normalize(make_powerlaw_csr(n=60 + 13 * i, seed=100 + i))
        key = (f"forced-{i}", cfg)
        plan = build_partition_plan(g, cfg)
        plan.key = key
        cache._placements[key] = target
        cache.shards[target].put(plan)
    # now any new key whose ring pick is the overloaded shard gets overridden
    before = cache.placement_overrides
    seen_override = False
    for i in range(40):
        key = (f"probe-{i}", cfg)
        dev = cache.device_index_of(key)
        if cache.ring.lookup(key[0]) == target:
            assert dev != target
            seen_override = True
    assert seen_override and cache.placement_overrides > before


def test_fleet_cache_placements_bounded_under_churn():
    """One-off graph churn must not leak placement entries: past 2x fleet
    capacity, placements of shard-evicted plans are pruned (so a rebuilt
    plan re-places with current load data)."""
    dev = jax.devices()[0]
    cache = FleetPlanCache([dev, dev], capacity_per_device=2)
    cfg = PartitionConfig()
    cap = 2 * cache.capacity_per_device * len(cache.shards)
    for i in range(6 * cap):
        g = gcn_normalize(make_powerlaw_csr(n=40 + i, seed=300 + i))
        cache.get_or_build(g, cfg)
        assert len(cache._placements) <= cap + 1
    # resident plans keep their placements
    for key in cache.keys():
        assert key in cache._placements


def test_fleet_engine_rejects_plain_plan_cache():
    from repro.core.plan_cache import PlanCache
    with pytest.raises(TypeError):
        FleetGraphEngine(cache=PlanCache(4))


# ---------------------------------------------------------------- routing
def test_route_fleet_strategies():
    # small resident dispatch, narrow, few blocks -> single
    fd = route_fleet(500, 16, 64, 32, num_blocks=6, n_devices=8)
    assert fd.strategy == "single" and fd.n_devices == 1
    # resident + narrow stays single even with many blocks: fits one
    # device's VMEM budget, nothing to save by sharding
    fd = route_fleet(3000, 16, 64, 32, num_blocks=169, n_devices=8)
    assert fd.strategy == "single"
    # wide features -> feature sharding with per-device share routed
    fd = route_fleet(500, 8 * 128, 64, 32, num_blocks=6, n_devices=8)
    assert fd.strategy == "feature" and fd.n_devices == 8
    assert fd.per_device.f_pad == 128
    # narrow GIANT graph (single-device estimate demotes off resident),
    # many blocks -> block sharding
    fd = route_fleet(20_000, 16, 64, 32, num_blocks=169, n_devices=8)
    assert fd.strategy == "block"
    assert fd.single.backend != "resident"
    # giant but too few blocks to give each device a share: still single
    fd = route_fleet(20_000, 16, 64, 32, num_blocks=4, n_devices=8)
    assert fd.strategy == "single"
    # one device: always single
    fd = route_fleet(20_000, 8 * 128, 64, 32, num_blocks=169, n_devices=1)
    assert fd.strategy == "single"


def test_route_fleet_multihost():
    """n_hosts > 1 routes over the GLOBAL mesh: block sharding spans hosts
    (psum returns a replicated answer), feature sharding is disabled
    (column-sharded output would pay a cross-host gather per answer)."""
    # narrow giant over 2 hosts x 4 devices: block-shard the global mesh
    fd = route_fleet(20_000, 16, 64, 32, num_blocks=169, n_devices=8,
                     n_hosts=2)
    assert fd.strategy == "block" and fd.n_hosts == 2
    assert fd.n_devices == 8
    assert "host" in fd.describe()
    # wide features, multi-host: NOT feature-sharded — stays single
    fd = route_fleet(500, 8 * 128, 64, 32, num_blocks=6, n_devices=8,
                     n_hosts=2)
    assert fd.strategy == "single"
    # same shape on one host still feature-shards (unchanged behavior)
    fd = route_fleet(500, 8 * 128, 64, 32, num_blocks=6, n_devices=8,
                     n_hosts=1)
    assert fd.strategy == "feature" and fd.n_hosts == 1
    # too few blocks for the global device count: single
    fd = route_fleet(20_000, 16, 64, 32, num_blocks=16, n_devices=8,
                     n_hosts=2)
    assert fd.strategy == "single"
    with pytest.raises(ValueError):
        route_fleet(500, 16, 64, 32, num_blocks=6, n_devices=8, n_hosts=0)


# ------------------------------------------------------- sharded dispatch
def test_feature_sharded_matches_blocked():
    g, plan = _plan()
    rng = np.random.default_rng(0)
    mesh = graph_mesh()
    d = mesh.devices.size
    # a width that does NOT divide the mesh exercises the pad/slice path
    for F in (d * 8, d * 8 + 3, 5):
        x = jnp.asarray(rng.normal(size=(g.n_cols, F)), jnp.float32)
        ref = spmm_blocked(plan.slabs["colidx"], plan.slabs["values"],
                           plan.slabs["rowloc"], plan.slabs["out_row"],
                           x, plan.n_rows)
        out = spmm_feature_sharded(plan.slabs, x, plan.n_rows, mesh)
        assert out.shape == (plan.n_rows, F)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_block_sharded_matches_blocked_and_reports_balance():
    g, plan = _plan(n=900, e=6000, seed=2)
    rng = np.random.default_rng(1)
    mesh = graph_mesh()
    x = jnp.asarray(rng.normal(size=(g.n_cols, 24)), jnp.float32)
    ref = spmm_blocked(plan.slabs["colidx"], plan.slabs["values"],
                       plan.slabs["rowloc"], plan.slabs["out_row"],
                       x, plan.n_rows)
    out, live = spmm_block_sharded(plan.slabs, x, plan.n_rows, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    assert live.sum() == plan.num_blocks
    assert live.max() - live.min() <= 1


# ------------------------------------------------------------- fleet engine
def _mixed_traffic_engines(n_graphs=5, feat=16):
    fleet = FleetGraphEngine(backend="blocked", max_graphs_per_batch=4)
    single = GraphServeEngine(backend="blocked", max_graphs_per_batch=4)
    feats = {}
    rng = np.random.default_rng(0)
    for i in range(n_graphs):
        gid = f"g{i}"
        g = gcn_normalize(make_power_law_graph(180 + 40 * i, 1200 + 90 * i,
                                               seed=i))
        fleet.register_graph(gid, g)
        single.register_graph(gid, g)
        feats[gid] = jnp.asarray(rng.normal(size=(g.n_cols, feat + 4 * i)),
                                 jnp.float32)
    return fleet, single, feats


def test_fleet_engine_matches_single_device_engine():
    """Acceptance: fleet-served outputs == single-device serving (fp tol)."""
    fleet, single, feats = _mixed_traffic_engines()
    try:
        freqs = fleet.serve([GraphRequest(gid, x) for gid, x in feats.items()])
        sreqs = single.serve([GraphRequest(gid, x) for gid, x in feats.items()])
        for fr, sr in zip(freqs, sreqs):
            np.testing.assert_allclose(np.asarray(fr.out), np.asarray(sr.out),
                                       atol=1e-4, rtol=1e-4)
        st_ = fleet.stats()
        assert st_["requests_served"] == len(feats)
        assert st_["fleet_rounds"] >= 1
        assert sum(st_["fleet_device_dispatches"]) >= 1
        # every request was answered by exactly one device's dispatch
        assert sum(st_["fleet_device_requests"]) == len(feats)
    finally:
        fleet.close()
        single.close()


def test_fleet_engine_concurrent_submitters_coalesce():
    fleet, single, feats = _mixed_traffic_engines(n_graphs=4)
    single.close()
    outs = {}
    try:
        def submitter(gid):
            outs[gid] = fleet.submit(gid, feats[gid])
        threads = [threading.Thread(target=submitter, args=(gid,))
                   for gid in feats]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for gid, fut in outs.items():
            direct = fleet.serve_one(gid, feats[gid])
            np.testing.assert_allclose(np.asarray(fut.result()),
                                       np.asarray(direct),
                                       atol=1e-4, rtol=1e-4)
        st_ = fleet.stats()
        assert st_["sched_completed"] == 2 * len(feats)
        assert st_["fleet_graphs_per_round"] >= 1.0
    finally:
        fleet.close()


def test_fleet_engine_giant_graph_block_shards():
    """A narrow giant graph (past the resident VMEM cap on one device)
    takes the block-sharded whole-mesh path and the engine exports its
    per-device balance evidence."""
    big = gcn_normalize(make_power_law_graph(6000, 40000, seed=5))
    fleet = FleetGraphEngine(backend="blocked")
    single = GraphServeEngine(backend="blocked")
    try:
        plan = fleet.register_graph("big", big)
        single.register_graph("big", big)
        assert plan.num_blocks >= fleet.n_devices
        x = jnp.asarray(np.random.default_rng(2).normal(
            size=(big.n_cols, 16)), jnp.float32)
        out = fleet.serve_one("big", x)
        ref = single.serve_one("big", x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)
        st_ = fleet.stats()
        # routed_* accounts for every dispatch, sharded ones included
        assert (st_["routed_resident"] + st_["routed_windowed"]
                + st_["routed_hbm"] + st_["routed_blocked"]
                == st_["batches_dispatched"])
        if fleet.n_devices > 1:
            assert st_["fleet_block_sharded"] == 1
            counts = st_["fleet_block_counts"]
            assert len(counts) == fleet.n_devices
            assert sum(counts) == plan.num_blocks
            # acceptance: balanced within 10% of the per-device mean
            assert st_["fleet_block_balance"] <= 1.10
        else:
            assert st_["fleet_block_sharded"] == 0  # degenerate 1-dev mesh
    finally:
        fleet.close()
        single.close()


def test_fleet_engine_validation_and_unknown_graph():
    fleet = FleetGraphEngine(backend="blocked")
    try:
        with pytest.raises(KeyError):
            fleet.submit("nope", jnp.zeros((4, 4)))
        g = gcn_normalize(make_powerlaw_csr(n=60, seed=0))
        fleet.register_graph("g", g)
        with pytest.raises(ValueError):
            fleet.submit("g", jnp.zeros((g.n_cols + 1, 4)))
    finally:
        fleet.close()


# -------------------------------------------------- real 8-device coverage
_EIGHT_DEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys, json
    sys.path.insert(0, "src")
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.graph import gcn_normalize
    from repro.data.graphs import make_power_law_graph
    from repro.serve.fleet import FleetGraphEngine
    from repro.serve.graph_engine import GraphRequest, GraphServeEngine

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(0)
    fleet = FleetGraphEngine(backend="blocked", max_graphs_per_batch=4)
    single = GraphServeEngine(backend="blocked", max_graphs_per_batch=4)
    feats = {}
    for i in range(4):
        gid = f"g{i}"
        g = gcn_normalize(make_power_law_graph(150 + 30 * i, 900 + 80 * i,
                                               seed=i))
        fleet.register_graph(gid, g)
        single.register_graph(gid, g)
        feats[gid] = jnp.asarray(rng.normal(size=(g.n_cols, 12)), jnp.float32)
    fr = fleet.serve([GraphRequest(g, x) for g, x in feats.items()])
    sr = single.serve([GraphRequest(g, x) for g, x in feats.items()])
    for a, b in zip(fr, sr):
        np.testing.assert_allclose(np.asarray(a.out), np.asarray(b.out),
                                   atol=1e-4, rtol=1e-4)

    big = gcn_normalize(make_power_law_graph(6000, 30000, seed=9))
    plan = fleet.register_graph("big", big)
    single.register_graph("big", big)
    xb = jnp.asarray(rng.normal(size=(big.n_cols, 16)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(fleet.serve_one("big", xb)),
        np.asarray(single.serve_one("big", xb)), atol=1e-4, rtol=1e-4)
    st = fleet.stats()
    fleet.close(); single.close()
    print(json.dumps({
        "devices": st["fleet_devices"],
        "block_sharded": st["fleet_block_sharded"],
        "block_counts": st["fleet_block_counts"],
        "block_balance": st["fleet_block_balance"],
        "device_requests": st["fleet_device_requests"],
        "num_blocks": plan.num_blocks,
    }))
""")


def test_fleet_on_eight_fake_devices_subprocess():
    """Real 8-device semantics regardless of how the suite itself was run
    (subprocess so the XLA flag cannot leak into other tests)."""
    proc = subprocess.run(
        [sys.executable, "-c", _EIGHT_DEV_SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stderr[-4000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 8
    assert rec["block_sharded"] == 1
    assert sum(rec["block_counts"]) == rec["num_blocks"]
    assert rec["block_balance"] <= 1.10
    assert max(rec["block_counts"]) - min(rec["block_counts"]) <= 1


# ------------------------------------------------------------ replica sets
def test_fleet_cache_add_drop_replica_roundtrip():
    """Replica copies are independent per-shard clones: the primary stays
    authoritative, extras stage/drop without touching it."""
    dev = jax.devices()[0]
    cache = FleetPlanCache([dev, dev], capacity_per_device=8)
    cfg = PartitionConfig()
    g = gcn_normalize(make_powerlaw_csr(n=90, seed=5))
    plan = cache.get_or_build(g, cfg)
    primary = cache.device_index_of(plan.key)
    other = 1 - primary
    assert cache.replica_devices(plan.key) == [primary]

    assert cache.add_replica(plan.key, other) is True
    assert cache.add_replica(plan.key, other) is True   # idempotent
    assert cache.replica_devices(plan.key) == [primary, other]
    copy = cache.plan_on(plan.key, other)
    assert copy is not None and copy is not plan, \
        "replica must be its own staged clone, not the primary object"
    import numpy as np
    np.testing.assert_array_equal(np.asarray(copy.slabs["colidx"]),
                                  np.asarray(plan.slabs["colidx"]))

    # the primary slot can never be dropped through the replica API
    assert cache.drop_replica(plan.key, primary) is False
    assert cache.drop_replica(plan.key, other) is True
    assert cache.replica_devices(plan.key) == [primary]
    assert cache.plan_on(plan.key, other) is None
    st = cache.stats()
    assert st["replicas_added"] == 1 and st["replicas_removed"] == 1

    # replicating a key with no resident primary plan is refused
    assert cache.add_replica(("ghost", cfg), other) is False


def test_fleet_cache_prune_is_replica_aware():
    """Placement pruning must not forget a key whose plan is resident only
    on a replica shard (regression: pruning used to consult the primary
    shard alone, so a replicated-but-primary-evicted plan lost its
    placement and its replicas became unreachable)."""
    dev = jax.devices()[0]
    cache = FleetPlanCache([dev, dev], capacity_per_device=2)
    cfg = PartitionConfig()
    g = gcn_normalize(make_powerlaw_csr(n=90, seed=6))
    plan = cache.get_or_build(g, cfg)
    primary = cache.device_index_of(plan.key)
    other = 1 - primary
    assert cache.add_replica(plan.key, other)
    # evict the PRIMARY copy (LRU churn elsewhere would do the same)
    assert cache.shards[primary].remove(plan.key)
    # churn one-off plans far past the pruning threshold
    for i in range(8 * 2 * cache.capacity_per_device * len(cache.shards)):
        cache.device_index_of((f"churn-{i}", cfg))
    assert plan.key in cache._placements, \
        "replica-resident key lost its placement to pruning"
    assert other in cache.replica_devices(plan.key)
    assert cache.plan_on(plan.key, other) is not None


_ZIPF_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys, json, threading
    sys.path.insert(0, "src")
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.graph import gcn_normalize
    from repro.data.graphs import make_power_law_graph
    from repro.serve.fleet import FleetGraphEngine

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(3)
    graphs = {f"z{i}": gcn_normalize(make_power_law_graph(
        220 + 40 * i, 1500 + 150 * i, seed=50 + i)) for i in range(5)}
    feats = {k: jnp.asarray(rng.normal(size=(g.n_cols, 16)), jnp.float32)
             for k, g in graphs.items()}
    names = list(graphs)
    p = np.arange(1, len(names) + 1, dtype=np.float64) ** -1.6
    p /= p.sum()
    schedule = [names[i] for i in
                rng.choice(len(names), size=96, p=p)]

    def run(**kw):
        e = FleetGraphEngine(max_batch_requests=32, max_wait_ms=3.0,
                             max_graphs_per_batch=1, backend="blocked", **kw)
        for k, g in graphs.items():
            e.register_graph(k, g)

        def pass_once():
            futs = [[] for _ in range(4)]
            def sub(t):
                futs[t] = [e.submit(gid, feats[gid])
                           for gid in schedule[t::4]]
            ths = [threading.Thread(target=sub, args=(t,)) for t in range(4)]
            for t in ths: t.start()
            for t in ths: t.join()
            return [np.asarray(f.result()) for fs in futs for f in fs]

        pass_once()              # warm: learn rates, stage replicas
        e.reset_stats()
        outs = pass_once()       # measured: replicated steady state
        st = e.stats()
        e.close()
        return outs, st

    outs_rep, st_rep = run(rate_per_replica=1.0, max_replicas=8,
                           replica_halflife_s=4.0,
                           replication_interval_s=0.005,
                           split_min_requests=1)
    outs_dis, st_dis = run(replicate_hot=False)
    for a, b in zip(outs_rep, outs_dis):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
    print(json.dumps({
        "promotions": st_rep["fleet_promotions"],
        "replicated_keys": st_rep["cache_replicated_keys"],
        "replica_copies": st_rep["cache_replica_copies"],
        "occ_rep": st_rep["fleet_occupancy"],
        "occ_dis": st_dis["fleet_occupancy"],
        "req_rep": st_rep["fleet_device_requests"],
        "req_dis": st_dis["fleet_device_requests"],
    }))
""")


def test_fleet_zipf_replication_subprocess():
    """Hot-plan replication under a zipf-skewed mix on 8 real fake devices:
    the hot plan promotes to >= 2 replicas, its traffic spreads across
    devices, fleet occupancy beats the single-owner run, and results match
    the replication-disabled engine exactly."""
    proc = subprocess.run(
        [sys.executable, "-c", _ZIPF_SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stderr[-4000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["promotions"] >= 1
    assert rec["replicated_keys"] >= 1
    assert rec["replica_copies"] >= 1, \
        "hot plan never reached a second replica"
    # replication spreads the zipf mix over strictly more devices than the
    # single-owner placement uses
    assert (len([r for r in rec["req_rep"] if r > 0])
            > len([r for r in rec["req_dis"] if r > 0]))
    # and the measured occupancy window must improve materially
    assert rec["occ_rep"] >= 1.5 * rec["occ_dis"], rec
