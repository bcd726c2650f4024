"""PlanCache semantics: hit/miss/build counters, LRU eviction, key hygiene."""
import numpy as np
import pytest

from repro.core.graph import gcn_normalize
from repro.core.plan_cache import (
    PartitionConfig, PlanCache, build_partition_plan, graph_content_hash,
)
from repro.core.spmm import make_accel_spmm
from repro.models.gcn import GraphOp

from conftest import make_powerlaw_csr


def _g(seed, n=120):
    return gcn_normalize(make_powerlaw_csr(n=n, seed=seed))


# ---------------------------------------------------------------------------
# content hash
# ---------------------------------------------------------------------------
def test_hash_deterministic_and_distinct():
    g1, g2 = _g(0), _g(1)
    assert graph_content_hash(g1) == graph_content_hash(_g(0))
    assert graph_content_hash(g1) != graph_content_hash(g2)


def test_hash_sensitive_to_values_not_just_structure():
    g = _g(3)
    g2 = type(g)(rowptr=g.rowptr, colidx=g.colidx,
                 values=g.values * 2.0, n_cols=g.n_cols)
    assert graph_content_hash(g) != graph_content_hash(g2)


def test_same_shape_different_colidx_distinct():
    # identical rowptr/values envelope, permuted column targets -> distinct
    a = make_powerlaw_csr(n=80, seed=10)
    b = type(a)(rowptr=a.rowptr, colidx=(a.colidx + 1) % a.n_cols,
                values=a.values, n_cols=a.n_cols)
    assert graph_content_hash(a) != graph_content_hash(b)


# ---------------------------------------------------------------------------
# hit / miss / build counters
# ---------------------------------------------------------------------------
def test_counters_hit_miss_build():
    cache = PlanCache(capacity=4)
    g = _g(0)
    cfg = PartitionConfig()
    p1 = cache.get_or_build(g, cfg)
    assert (cache.hits, cache.misses, cache.builds) == (0, 1, 1)
    p2 = cache.get_or_build(g, cfg)
    assert (cache.hits, cache.misses, cache.builds) == (1, 1, 1)
    assert p1 is p2, "hit must return the SAME staged plan object"
    st = cache.stats()
    assert st["hit_rate"] == pytest.approx(0.5)
    assert st["size"] == 1 and st["device_bytes"] > 0


def test_config_is_part_of_key():
    cache = PlanCache(capacity=8)
    g = _g(2)
    cache.get_or_build(g, PartitionConfig(mode="tpu"))
    cache.get_or_build(g, PartitionConfig(mode="paper", max_block_warps=8,
                                          max_warp_nzs=16))
    cache.get_or_build(g, PartitionConfig(mode="tpu", max_block_warps=32,
                                          max_warp_nzs=8))
    assert cache.builds == 3 and cache.hits == 0


# ---------------------------------------------------------------------------
# LRU eviction
# ---------------------------------------------------------------------------
def test_lru_eviction_order():
    cache = PlanCache(capacity=2)
    cfg = PartitionConfig()
    g0, g1, g2 = _g(0), _g(1), _g(2)
    k0 = (graph_content_hash(g0), cfg)
    cache.get_or_build(g0, cfg)
    cache.get_or_build(g1, cfg)
    cache.get_or_build(g0, cfg)          # refresh g0 -> g1 is now LRU
    cache.get_or_build(g2, cfg)          # evicts g1
    assert cache.evictions == 1 and len(cache) == 2
    assert k0 in cache
    assert (graph_content_hash(g1), cfg) not in cache
    cache.get_or_build(g1, cfg)          # rebuilt: a miss, not a hit
    assert cache.builds == 4 and cache.evictions == 2


def test_capacity_one_thrash_still_correct():
    cache = PlanCache(capacity=1)
    cfg = PartitionConfig()
    for seed in (0, 1, 0, 1):
        p = cache.get_or_build(_g(seed), cfg)
        assert p.n_rows == 120
    assert cache.builds == 4 and cache.hits == 0 and cache.evictions == 3


# ---------------------------------------------------------------------------
# integration: operators and models through the cache
# ---------------------------------------------------------------------------
def test_make_accel_spmm_shares_plan():
    cache = PlanCache()
    g = _g(5)
    op1 = make_accel_spmm(g, plan_cache=cache)
    op2 = make_accel_spmm(g, plan_cache=cache)
    assert cache.builds == 1 and cache.hits == 1
    assert op1.plan is op2.plan
    # and cached operators still compute the right thing
    import jax.numpy as jnp
    from repro.kernels.ref import csr_spmm_ref
    X = jnp.asarray(np.random.default_rng(0).normal(size=(g.n_rows, 24)),
                    dtype=jnp.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, X))
    np.testing.assert_allclose(np.asarray(op2(X)), ref, atol=1e-3, rtol=1e-3)


def test_graphop_build_partitions_once_per_matrix():
    """Acceptance: serving the same graph twice partitions exactly once."""
    cache = PlanCache()
    g = _g(7)
    GraphOp.build(g, plan_cache=cache)        # builds A' and A'^T plans
    assert cache.builds == 2 and cache.misses == 2
    GraphOp.build(g, plan_cache=cache)        # all hits, zero new builds
    assert cache.builds == 2 and cache.hits == 2


def test_plan_roundtrip_without_cache_matches():
    g = _g(9)
    cfg = PartitionConfig()
    p_direct = build_partition_plan(g, cfg)
    p_cached = PlanCache().get_or_build(g, cfg)
    assert p_direct.key == p_cached.key
    assert p_direct.num_blocks == p_cached.num_blocks
    np.testing.assert_array_equal(np.asarray(p_direct.slabs["colidx"]),
                                  np.asarray(p_cached.slabs["colidx"]))


# ---------------------------------------------------------------------------
# thread safety (the serving schedulers hit the cache from flush threads)
# ---------------------------------------------------------------------------
def test_parallel_get_or_build_single_flight():
    """Satellite acceptance: N threads racing get_or_build of the SAME graph
    run the partition pipeline exactly once and share one plan object."""
    import threading
    cache = PlanCache(capacity=8)
    g, cfg = _g(21), PartitionConfig()
    plans = [None] * 8
    barrier = threading.Barrier(8)

    def worker(i):
        barrier.wait()
        plans[i] = cache.get_or_build(g, cfg)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cache.builds == 1, "parallel misses must coalesce into one build"
    assert cache.misses == 1 and cache.hits == 7
    assert all(p is plans[0] for p in plans)


def test_parallel_distinct_graphs_build_concurrently():
    import threading
    cache = PlanCache(capacity=8)
    cfg = PartitionConfig()
    gs = [_g(30 + i) for i in range(4)]
    threads = [threading.Thread(target=cache.get_or_build, args=(g, cfg))
               for g in gs for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert cache.builds == 4 and len(cache) == 4


# ---------------------------------------------------------------------------
# disk persistence: spill evicted plans, reload on miss
# ---------------------------------------------------------------------------
def test_evicted_plan_spills_and_reloads(tmp_path):
    cache = PlanCache(capacity=1, save_dir=str(tmp_path))
    cfg = PartitionConfig()
    g0, g1 = _g(0), _g(1)
    p0 = cache.get_or_build(g0, cfg)
    cache.get_or_build(g1, cfg)          # evicts g0 -> spills to disk
    st = cache.stats()
    assert st["evictions"] == 1 and st["spills"] == 1
    assert len(list(tmp_path.glob("*.npz"))) == 1

    p0b = cache.get_or_build(g0, cfg)    # miss -> disk reload, NOT a rebuild
    st = cache.stats()
    assert st["disk_hits"] == 1
    assert st["builds"] == 2, "disk hit must not re-run the partition"
    assert p0b.key == p0.key
    assert p0b.num_blocks == p0.num_blocks
    for k in ("colidx", "values", "rowloc", "out_row"):
        np.testing.assert_array_equal(np.asarray(p0b.slabs[k]),
                                      np.asarray(p0.slabs[k]))
    np.testing.assert_array_equal(np.asarray(p0b.inv_perm),
                                  np.asarray(p0.inv_perm))
    np.testing.assert_array_equal(p0b.partition.meta, p0.partition.meta)


def test_reloaded_plan_computes_correctly(tmp_path):
    import jax.numpy as jnp
    from repro.kernels.ref import csr_spmm_ref
    cache = PlanCache(capacity=1, save_dir=str(tmp_path))
    cfg = PartitionConfig()
    g = _g(5)
    cache.get_or_build(g, cfg)
    cache.get_or_build(_g(6), cfg)       # evict + spill g
    cache.get_or_build(g, cfg)           # reload from disk
    op = make_accel_spmm(g, plan_cache=cache)
    X = jnp.asarray(np.random.default_rng(0).normal(size=(g.n_rows, 12)),
                    dtype=jnp.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, X))
    np.testing.assert_allclose(np.asarray(op(X)), ref, atol=1e-3, rtol=1e-3)


def test_corrupt_spill_falls_back_to_rebuild(tmp_path):
    cache = PlanCache(capacity=1, save_dir=str(tmp_path))
    cfg = PartitionConfig()
    g0 = _g(0)
    cache.get_or_build(g0, cfg)
    cache.get_or_build(_g(1), cfg)       # evict + spill g0
    spill = next(tmp_path.glob("*.npz"))
    spill.write_bytes(b"not a real npz")
    p = cache.get_or_build(g0, cfg)      # must rebuild, not crash
    assert p.n_rows == g0.n_rows
    assert cache.stats()["disk_hits"] == 0
    assert cache.builds == 3


def _scattered_repair(plan):
    """``plan`` as an older plan repair left it: two rows of one degree,
    each off local row 0 of a block of several rows, masked in place (holes)
    and re-emitted together in one appended block at positions that are not
    consecutive. The segment sum still answers right; the slab layout the
    kernels fold does not hold."""
    import dataclasses
    import jax.numpy as jnp
    out_row = np.asarray(plan.slabs["out_row"]).copy()
    colidx, values, rowloc = (np.asarray(plan.slabs[k]) for k in
                              ("colidx", "values", "rowloc"))
    n, R, C = plan.n_rows, int(plan.slabs["R"]), int(plan.slabs["C"])
    deg = plan.partition.meta[:, 0]
    many = (out_row[:, 2] != n) & ~plan.partition.is_split
    d = int(np.bincount(deg[many]).argmax())
    (b1, b2) = np.flatnonzero(many & (deg == d))[[0, -1]]
    new = [np.zeros(C, np.int32), np.zeros(C, np.float32),
           np.full(C, R - 1, np.int32), np.full(R, n, np.int32)]
    for j, b in enumerate((b1, b2)):
        lane = rowloc[b] == 1
        new[0][j * d:(j + 1) * d] = colidx[b][lane]
        new[1][j * d:(j + 1) * d] = values[b][lane]
        new[2][j * d:(j + 1) * d] = j
        new[3][j] = out_row[b, 1]
        out_row[b, 1] = n
    slabs = dict(plan.slabs)
    for k, a, extra in zip(("colidx", "values", "rowloc", "out_row"),
                           (colidx, values, rowloc, out_row), new):
        slabs[k] = jnp.asarray(np.concatenate([a, extra[None]]))
    return dataclasses.replace(plan, slabs=slabs)


def test_spill_of_an_older_repair_layout_is_rebuilt(tmp_path):
    """A spilled plan whose slabs lack the layout the kernels fold (holes in
    a block, a block at scattered rows, as an older plan repair left them)
    is not loaded: it is deleted and rebuilt, and the rebuilt plan answers
    as the graph does and spills again under the same name."""
    import jax.numpy as jnp
    from repro.core.partition import slab_layout_ok
    from repro.kernels.ops import spmm_batched
    from repro.kernels.ref import csr_spmm_ref, slab_spmm_ref
    cfg = PartitionConfig(max_block_warps=4, max_warp_nzs=2)
    g = _g(7)
    old = _scattered_repair(build_partition_plan(g, cfg))
    sl = [old.slabs[k] for k in ("colidx", "values", "rowloc", "out_row")]
    assert not slab_layout_ok(old.slabs["out_row"], old.n_rows)
    X = jnp.asarray(np.random.default_rng(0).normal(size=(g.n_rows, 8)),
                    dtype=jnp.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, X))
    perm = np.asarray(old.inv_perm)
    np.testing.assert_allclose(
        np.asarray(slab_spmm_ref(*sl, X, old.n_rows))[perm], ref,
        atol=1e-4, rtol=1e-4)

    cache = PlanCache(capacity=1, save_dir=str(tmp_path))
    cache.put(old)
    cache.get_or_build(_g(8), cfg)       # evicts + spills the old layout
    assert cache.stats()["spills"] == 1
    plan = cache.get_or_build(g, cfg)    # refused on load: rebuilt
    st = cache.stats()
    assert (st["spill_rejects"], st["disk_hits"], st["builds"]) == (1, 0, 2)
    assert not list(tmp_path.glob(f"{plan.key[0]}-*.npz"))
    assert slab_layout_ok(plan.slabs["out_row"], plan.n_rows)
    got = spmm_batched([plan.slabs], [X], [plan.n_rows], backend="pallas")[0]
    np.testing.assert_allclose(np.asarray(got)[np.asarray(plan.inv_perm)],
                               ref, atol=1e-4, rtol=1e-4)

    cache.get_or_build(_g(8), cfg)       # the rebuilt plan spills ...
    again = cache.get_or_build(g, cfg)   # ... and reloads from disk
    st = cache.stats()                   # (so do both of _g(8)'s reloads)
    assert (st["spill_rejects"], st["disk_hits"], st["builds"]) == (1, 2, 2)
    np.testing.assert_array_equal(np.asarray(again.slabs["out_row"]),
                                  np.asarray(plan.slabs["out_row"]))


def test_config_tag_distinguishes_spills(tmp_path):
    cache = PlanCache(capacity=1, save_dir=str(tmp_path))
    g = _g(3)
    cache.get_or_build(g, PartitionConfig(mode="tpu"))
    cache.get_or_build(g, PartitionConfig(mode="tpu", max_block_warps=32))
    # second build evicted+spilled the first; same graph hash, distinct tag
    names = {p.name for p in tmp_path.glob("*.npz")}
    assert len(names) == 1
    cache.get_or_build(_g(4), PartitionConfig(mode="tpu", max_block_warps=32))
    assert len(list(tmp_path.glob("*.npz"))) == 2


# ---------------------------------------------------------------------------
# stats atomicity
# ---------------------------------------------------------------------------
def test_stats_snapshot_atomic_under_hammering_thread():
    """Regression: ``stats()`` is one consistent snapshot taken under the
    cache lock. ``lookups`` is bumped in the SAME lock hold as ``hits`` /
    ``misses``, so any torn read (counters sampled at two different moments
    while a flush thread mutates them) would show up as
    ``hits + misses != lookups`` or an out-of-range derived value."""
    import threading

    cfg = PartitionConfig()
    cache = PlanCache(capacity=4)
    graphs = [_g(200 + i, n=60) for i in range(8)]  # > capacity: evictions too
    stop = threading.Event()
    errors = []

    def hammer(tid):
        k = 0
        while not stop.is_set():
            cache.get_or_build(graphs[(tid + k) % len(graphs)], cfg)
            k += 1

    def sampler():
        while not stop.is_set():
            s = cache.stats()
            try:
                assert s["hits"] + s["misses"] == s["lookups"], s
                assert 0.0 <= s["hit_rate"] <= 1.0
                assert s["size"] <= s["capacity"]
                assert s["builds"] + s["disk_hits"] <= s["misses"]
            except AssertionError as e:
                errors.append(e)
                return

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(3)]
    threads += [threading.Thread(target=sampler) for _ in range(2)]
    for t in threads:
        t.start()
    import time
    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, f"torn stats snapshot observed: {errors[0]}"
    # quiesced: the invariant holds exactly
    s = cache.stats()
    assert s["hits"] + s["misses"] == s["lookups"]
