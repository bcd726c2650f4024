"""GraphServeEngine: correctness, batching behavior, cache amortization."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import gcn_normalize
from repro.core.plan_cache import PlanCache
from repro.core.spmm import make_accel_spmm
from repro.serve.graph_engine import GraphRequest, GraphServeEngine

from conftest import make_powerlaw_csr, make_wide_csr


def _setup(n_graphs=3, backend="blocked", **ekw):
    engine = GraphServeEngine(backend=backend, **ekw)
    graphs, feats = {}, {}
    rng = np.random.default_rng(0)
    for i in range(n_graphs):
        gid = f"g{i}"
        g = gcn_normalize(make_powerlaw_csr(n=90 + 25 * i, seed=i))
        engine.register_graph(gid, g)
        graphs[gid] = g
        feats[gid] = jnp.asarray(rng.normal(size=(g.n_rows, 16 + 8 * i)),
                                 dtype=jnp.float32)
    return engine, graphs, feats


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["blocked", "pallas", "auto"])
def test_serve_matches_direct_operator(backend):
    engine, graphs, feats = _setup(backend=backend)
    reqs = [GraphRequest(gid, feats[gid]) for gid in graphs]
    engine.serve(reqs)
    for r in reqs:
        direct = make_accel_spmm(graphs[r.graph_id])(feats[r.graph_id])
        np.testing.assert_allclose(np.asarray(r.out), np.asarray(direct),
                                   atol=1e-4, rtol=1e-4)
        assert r.latency_s is not None and r.latency_s > 0


def test_same_graph_served_twice_partitions_once():
    """Acceptance criterion, end to end through the engine."""
    engine, graphs, feats = _setup(n_graphs=1)
    builds_after_register = engine.cache.builds
    assert builds_after_register == 1
    engine.serve([GraphRequest("g0", feats["g0"])])
    engine.serve([GraphRequest("g0", feats["g0"] * 2)])
    assert engine.cache.builds == 1, "serving must never re-partition"
    assert engine.cache.hits >= 2


def test_same_graph_requests_fuse_along_features():
    """N same-graph requests -> one dispatch; each gets its own columns back."""
    engine, graphs, feats = _setup(n_graphs=1)
    x = feats["g0"]
    reqs = [GraphRequest("g0", x),
            GraphRequest("g0", 3.0 * x),
            GraphRequest("g0", x[:, :5])]
    engine.serve(reqs)
    assert engine.batches_dispatched == 1
    assert engine.requests_served == 3
    direct = make_accel_spmm(graphs["g0"])
    np.testing.assert_allclose(np.asarray(reqs[0].out),
                               np.asarray(direct(x)), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(reqs[1].out),
                               np.asarray(direct(3.0 * x)),
                               atol=1e-4, rtol=1e-4)
    assert reqs[2].out.shape == (graphs["g0"].n_rows, 5)
    np.testing.assert_allclose(np.asarray(reqs[2].out),
                               np.asarray(direct(x[:, :5])),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_batch_splitting_respects_max_graphs():
    engine, graphs, feats = _setup(n_graphs=5, max_graphs_per_batch=2)
    reqs = [GraphRequest(gid, feats[gid]) for gid in graphs]
    engine.serve(reqs)
    assert engine.batches_dispatched == 3  # ceil(5 / 2)
    for r in reqs:
        direct = make_accel_spmm(graphs[r.graph_id])(feats[r.graph_id])
        np.testing.assert_allclose(np.asarray(r.out), np.asarray(direct),
                                   atol=1e-4, rtol=1e-4)


def test_unknown_graph_rejected():
    engine, _, feats = _setup(n_graphs=1)
    with pytest.raises(KeyError, match="not registered"):
        engine.serve([GraphRequest("nope", feats["g0"])])


def test_bad_feature_shape_rejected():
    engine, graphs, _ = _setup(n_graphs=1)
    wrong = jnp.zeros((graphs["g0"].n_rows + 1, 4), jnp.float32)
    with pytest.raises(ValueError, match="expected"):
        engine.serve([GraphRequest("g0", wrong)])


def test_malformed_request_fails_before_any_dispatch():
    """Validation is all-or-nothing: a bad request in a later batch must not
    leave earlier batches served and counters mutated."""
    engine, graphs, feats = _setup(n_graphs=3, max_graphs_per_batch=1)
    bad = jnp.zeros((5, 5), jnp.float32)
    reqs = [GraphRequest("g0", feats["g0"]),
            GraphRequest("g1", feats["g1"]),
            GraphRequest("g2", bad)]
    with pytest.raises(ValueError, match="expected"):
        engine.serve(reqs)
    assert engine.batches_dispatched == 0
    assert engine.requests_served == 0
    assert all(r.out is None for r in reqs)


def test_serve_does_not_rehash_registered_graphs(monkeypatch):
    """Steady-state dispatches must not recompute the content hash."""
    import repro.core.plan_cache as pc
    engine, graphs, feats = _setup(n_graphs=2)

    def boom(_g):
        raise AssertionError("content hash recomputed on the serve hot path")
    monkeypatch.setattr(pc, "graph_content_hash", boom)
    reqs = [GraphRequest(gid, feats[gid]) for gid in graphs]
    engine.serve(reqs)
    assert all(r.out is not None for r in reqs)


def test_stats_accumulate_and_cache_is_shared():
    shared = PlanCache(capacity=8)
    engine = GraphServeEngine(cache=shared, backend="blocked")
    g = gcn_normalize(make_powerlaw_csr(n=70, seed=9))
    engine.register_graph("a", g)
    x = jnp.ones((g.n_rows, 4), jnp.float32)
    engine.serve([GraphRequest("a", x)])
    engine.serve([GraphRequest("a", x)])
    st = engine.stats()
    assert st["requests_served"] == 2
    assert st["batches_dispatched"] == 2
    assert st["rows_served"] == 2 * g.n_rows
    assert st["total_serve_s"] > 0 and st["rows_per_s"] > 0
    assert st["cache_builds"] == 1 and st["cache_hits"] >= 2
    # the same external cache also serves non-engine callers without rebuild
    make_accel_spmm(g, plan_cache=shared)
    assert shared.builds == 1


def test_reregister_same_content_is_noop_hit():
    engine, graphs, _ = _setup(n_graphs=1)
    assert engine.cache.builds == 1
    engine.register_graph("g0", graphs["g0"])
    assert engine.cache.builds == 1 and engine.cache.hits >= 1


def test_serve_one_convenience():
    engine, graphs, feats = _setup(n_graphs=1)
    out = engine.serve_one("g0", feats["g0"])
    direct = make_accel_spmm(graphs["g0"])(feats["g0"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------- routing + latency
def _large_mix_engine(backend):
    engine = GraphServeEngine(backend=backend)
    graphs = {"big": make_wide_csr(500, 20_000, 1_500, seed=1)}
    for i in range(3):
        graphs[f"s{i}"] = gcn_normalize(make_powerlaw_csr(n=80 + 20 * i,
                                                          seed=2 + i))
    for gid, g in graphs.items():
        engine.register_graph(gid, g)
    rng = np.random.default_rng(0)
    reqs = [GraphRequest(gid, jnp.asarray(
        rng.normal(size=(g.n_cols, 8)), jnp.float32))
        for gid, g in graphs.items()]
    return engine, graphs, reqs


@pytest.mark.slow
def test_engine_routes_oversized_batch_to_hbm():
    """Acceptance: a batch mixing one n_cols=20k graph with small graphs
    dispatches through the engine, routes to the HBM-gather backend, and
    matches the per-graph blocked oracle to <= 1e-5."""
    engine, graphs, reqs = _large_mix_engine("auto")
    engine.serve(reqs)
    st = engine.stats()
    assert st["routed_hbm"] == 1, "oversized batch must take the HBM path"
    assert st["routed_resident"] == st["routed_windowed"] == 0
    assert engine.last_decision.backend == "hbm"
    d = engine.last_decision
    assert d.vmem_bytes <= d.total_budget_bytes, \
        "dispatch exceeds the per-call VMEM estimate budget"
    for r in reqs:
        oracle = make_accel_spmm(graphs[r.graph_id], backend="blocked")(r.x)
        np.testing.assert_allclose(np.asarray(r.out), np.asarray(oracle),
                                   atol=1e-5, rtol=1e-5)


def test_engine_forced_resident_raises_budget_error():
    """Acceptance: backend='pallas' on the same oversized batch raises the
    budget error instead of silently compiling, serving nothing."""
    from repro.kernels.router import VmemBudgetError
    engine, _, reqs = _large_mix_engine("pallas")
    with pytest.raises(VmemBudgetError, match="VMEM budget"):
        engine.serve(reqs)
    assert engine.batches_dispatched == 0
    assert all(r.out is None for r in reqs)


def test_engine_small_batches_route_resident():
    engine, graphs, feats = _setup(n_graphs=3, backend="auto")
    engine.serve([GraphRequest(gid, feats[gid]) for gid in graphs])
    st = engine.stats()
    assert st["routed_resident"] == 1
    assert st["routed_hbm"] == st["routed_windowed"] == 0


def test_blocked_backend_counts_as_blocked_dispatch():
    engine, graphs, feats = _setup(n_graphs=1, backend="blocked")
    engine.serve([GraphRequest("g0", feats["g0"])])
    assert engine.stats()["routed_blocked"] == 1


def test_hbm_gather_passes_count_f_pad_over_gather_width():
    """Each hbm dispatch adds F_pad / W row-gather passes: one at full width
    (F=16, F=256), seventeen past the 2 MiB gather budget (F=2100: F_pad
    2176, W=128, with features left unbucketed); dispatches on other
    kernels add none."""
    engine, graphs, feats = _setup(n_graphs=1, backend="hbm",
                                   feature_bucket=False)
    g = graphs["g0"]
    rng = np.random.default_rng(1)
    passes = []
    for f in (16, 256, 2100):
        x = jnp.asarray(rng.normal(size=(g.n_rows, f)), jnp.float32)
        req = GraphRequest("g0", x)
        engine.serve([req])
        np.testing.assert_allclose(
            np.asarray(req.out), np.asarray(make_accel_spmm(g)(x)),
            atol=1e-4, rtol=1e-4)
        passes.append(engine.stats()["hbm_gather_passes"])
    assert passes == [1, 2, 19]
    assert engine.stats()["routed_hbm"] == 3
    other, _, other_feats = _setup(n_graphs=1, backend="pallas")
    other.serve([GraphRequest("g0", other_feats["g0"])])
    assert other.stats()["hbm_gather_passes"] == 0


def test_per_request_latency_includes_queue_wait():
    """Requests answered by later dispatches of one serve() call must report
    strictly larger enqueue->answer latency than the first dispatch; the
    per-dispatch kernel time accumulates separately."""
    engine, graphs, feats = _setup(n_graphs=3, max_graphs_per_batch=1)
    reqs = [GraphRequest(gid, feats[gid]) for gid in graphs]
    engine.serve(reqs)
    assert engine.batches_dispatched == 3
    lat = [r.latency_s for r in reqs]
    assert all(l is not None and l > 0 for l in lat)
    assert lat[0] < lat[1] < lat[2], "later dispatches waited in queue"
    st = engine.stats()
    # queue wait means summed request latency exceeds summed kernel time
    assert engine.total_request_latency_s > st["total_serve_s"]
    assert st["avg_dispatch_s"] > 0
    assert st["avg_request_latency_s"] >= st["avg_dispatch_s"]


def test_block_padding_counters_visible():
    engine, graphs, feats = _setup(n_graphs=2)  # default bucket tiers from 8
    engine.serve([GraphRequest(gid, feats[gid]) for gid in graphs])
    st = engine.stats()
    assert st["live_blocks"] > 0
    assert st["padded_blocks"] >= st["live_blocks"]
    # power-of-two tiers bound waste by 2x (plus the min-tier floor of 8)
    assert st["padded_blocks"] < 2 * max(st["live_blocks"], 8)
    assert st["block_pad_ratio"] == st["padded_blocks"] / st["live_blocks"]


def test_bad_backend_rejected():
    with pytest.raises(ValueError, match="backend must be"):
        GraphServeEngine(backend="segment")


# ------------------------------------------------- continuous batching
def test_submit_future_matches_serve_one():
    engine, graphs, feats = _setup(n_graphs=1)
    fut = engine.submit("g0", feats["g0"])
    out = fut.result(timeout=60)
    direct = make_accel_spmm(graphs["g0"])(feats["g0"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(direct),
                               atol=1e-4, rtol=1e-4)
    engine.close()


def test_submit_validates_synchronously():
    engine, graphs, _ = _setup(n_graphs=1)
    with pytest.raises(KeyError, match="not registered"):
        engine.submit("nope", jnp.zeros((3, 3), jnp.float32))
    with pytest.raises(ValueError, match="expected"):
        engine.submit("g0", jnp.zeros((graphs["g0"].n_rows + 1, 4),
                                      jnp.float32))


def test_deadline_flush_fires_for_single_queued_request():
    """A lone submit() must be answered after ~max_wait_ms, not hang waiting
    for co-batchable traffic."""
    engine, graphs, feats = _setup(n_graphs=1, max_wait_ms=20.0)
    out = engine.submit("g0", feats["g0"]).result(timeout=60)
    assert out.shape == feats["g0"].shape
    st = engine.stats()
    assert st["sched_flush_deadline"] == 1
    assert st["sched_flush_size"] == 0
    engine.close()


def test_multithreaded_submit_parity_with_serve():
    """Satellite acceptance: concurrent submit() answers match synchronous
    serve() — same values, ORIGINAL row order — and cross-caller requests
    coalesce into shared fused dispatches."""
    engine, graphs, feats = _setup(n_graphs=3, max_wait_ms=60.0)
    n_threads, per_thread = 4, 6
    futs = [[None] * per_thread for _ in range(n_threads)]

    def caller(t):
        for k in range(per_thread):
            gid = f"g{(t + k) % len(graphs)}"
            futs[t][k] = (gid, float(t + 1),
                          engine.submit(gid, feats[gid] * (t + 1)))

    threads = [threading.Thread(target=caller, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    oracles = {gid: make_accel_spmm(graphs[gid]) for gid in graphs}
    for t in range(n_threads):
        for gid, scalef, fut in futs[t]:
            got = fut.result(timeout=120)
            want = oracles[gid](feats[gid] * scalef)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-4, rtol=1e-4)
    st = engine.stats()
    assert st["requests_served"] == n_threads * per_thread
    # the whole point: fewer dispatches than requests, multiple graphs per
    # fused dispatch (concurrent callers shared batches)
    assert st["batches_dispatched"] < n_threads * per_thread
    assert st["requests_per_batch"] > 1.0
    assert st["graphs_per_dispatch"] > 1.0
    engine.close()


def test_sync_serve_coalesces_with_async_submitters():
    """serve() is a wrapper over the same queue: its requests and a
    concurrent submit() can share one flush."""
    engine, graphs, feats = _setup(n_graphs=2, max_wait_ms=25.0)
    results = {}

    def sync_caller():
        reqs = [GraphRequest("g0", feats["g0"])]
        engine.serve(reqs)
        results["sync"] = reqs[0].out

    def async_caller():
        results["async"] = engine.submit("g1", feats["g1"]).result(timeout=60)

    ts = [threading.Thread(target=sync_caller),
          threading.Thread(target=async_caller)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for gid, key in (("g0", "sync"), ("g1", "async")):
        want = make_accel_spmm(graphs[gid])(feats[gid])
        np.testing.assert_allclose(np.asarray(results[key]),
                                   np.asarray(want), atol=1e-4, rtol=1e-4)
    engine.close()


def test_feature_bucketing_pads_fused_width_only():
    """Fused same-graph widths round to powers of two for jit reuse; the
    per-request outputs are still exactly the requested widths."""
    engine, graphs, feats = _setup(n_graphs=1)  # feature_bucket=True default
    x = feats["g0"]  # width 16
    reqs = [GraphRequest("g0", x), GraphRequest("g0", x[:, :5]),
            GraphRequest("g0", 2.0 * x[:, :7])]   # fused 28 -> padded 32
    engine.serve(reqs)
    assert engine.batches_dispatched == 1
    direct = make_accel_spmm(graphs["g0"])
    np.testing.assert_allclose(np.asarray(reqs[1].out),
                               np.asarray(direct(x[:, :5])),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(reqs[2].out),
                               np.asarray(direct(2.0 * x[:, :7])),
                               atol=1e-4, rtol=1e-4)
    assert reqs[1].out.shape[1] == 5 and reqs[2].out.shape[1] == 7
