"""Serve GCN inference over a fleet of graphs through GraphServeEngine.

    PYTHONPATH=src python examples/serve_gcn.py

Simulates the serving north star at desk scale: several distinct graphs,
repeated inference traffic. Every layer's aggregation A'.(XW) for ALL graphs
in flight goes through ONE fused multi-graph SpMM dispatch; partition plans
are built once per graph and then always hit the cache. The engine's answer
is checked against the direct single-graph GraphOp path.

The second half demonstrates the continuous-batching core: N caller
threads submit single requests (``engine.submit -> Future``) and the
background scheduler coalesces them into fused cross-caller dispatches —
the thing the old blocking ``serve()`` fundamentally could not do.

The final section applies a streaming edge delta (``mutate``) to a live
graph and checks the repaired plan against a fresh direct operator.
"""
import argparse
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import gcn_normalize
from repro.core.plan_cache import PartitionConfig
from repro.core.plan_repair import EdgeDelta
from repro.data.graphs import make_power_law_graph, node_features
from repro.models.gcn import GraphOp
from repro.models.layers import dense_init
from repro.serve.graph_engine import GraphRequest, GraphServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=4)
    ap.add_argument("--nodes", type=int, default=600)
    ap.add_argument("--edges", type=int, default=3600)
    ap.add_argument("--dims", type=int, nargs="+", default=[32, 64, 16])
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    engine = GraphServeEngine(config=PartitionConfig(), backend="blocked",
                              max_graphs_per_batch=4)
    graphs = {}
    for i in range(args.graphs):
        gid = f"g{i}"
        g = gcn_normalize(make_power_law_graph(
            args.nodes + 37 * i, args.edges + 101 * i, seed=i))
        engine.register_graph(gid, g)
        graphs[gid] = g
    print(f"[serve_gcn] registered {args.graphs} graphs; "
          f"cache builds={engine.cache.builds}")

    # One shared GCN weight stack (dims[0] -> ... -> dims[-1]).
    ks = jax.random.split(jax.random.PRNGKey(0), len(args.dims) - 1)
    weights = [dense_init(k, a, b, jnp.float32)
               for k, a, b in zip(ks, args.dims[:-1], args.dims[1:])]

    def engine_forward(feats):  # {gid: [N, F]} -> logits per graph
        h = dict(feats)
        for li, w in enumerate(weights):
            reqs = [GraphRequest(gid, jnp.dot(h[gid], w)) for gid in h]
            for r in engine.serve(reqs):
                h[r.graph_id] = (jax.nn.relu(r.out)
                                 if li < len(weights) - 1 else r.out)
        return h

    feats = {gid: jnp.asarray(node_features(g.n_rows, args.dims[0], seed=i))
             for i, (gid, g) in enumerate(graphs.items())}

    t0 = time.perf_counter()
    for rnd in range(args.rounds):
        logits = engine_forward(feats)
    dt = time.perf_counter() - t0

    # Cross-check one graph against the direct (unbatched) operator path.
    gid0 = next(iter(graphs))
    aggr = GraphOp.build(graphs[gid0], backend="blocked",
                         plan_cache=engine.cache)
    h = feats[gid0]
    for li, w in enumerate(weights):
        h = aggr(jnp.dot(h, w))
        if li < len(weights) - 1:
            h = jax.nn.relu(h)
    err = float(jnp.max(jnp.abs(h - logits[gid0])))
    assert err < 1e-3, f"engine vs direct mismatch: {err}"

    st = engine.stats()
    print(f"[serve_gcn] {args.rounds} rounds x {len(weights)} layers x "
          f"{args.graphs} graphs in {dt:.2f}s")
    print(f"[serve_gcn] batches={st['batches_dispatched']} "
          f"requests={st['requests_served']} "
          f"requests/batch={st['requests_per_batch']:.1f} "
          f"rows/s={st['rows_per_s']:.3g}")
    print(f"[serve_gcn] plan cache: builds={st['cache_builds']} "
          f"hits={st['cache_hits']} hit_rate={st['cache_hit_rate']:.3f} "
          f"(partitioned each graph exactly once)")
    print(f"[serve_gcn] engine vs direct GraphOp max|err| = {err:.2e}  OK")

    # ---- concurrent submitters: cross-caller continuous batching ---------
    base_batches = engine.batches_dispatched
    base_graphs = engine.graphs_dispatched
    n_threads, per_thread = 4, 6

    def caller(t):
        futs = []
        for k in range(per_thread):
            gid = f"g{(t + k) % args.graphs}"
            futs.append(engine.submit(gid, jnp.dot(feats[gid], weights[0])))
        for f in futs:
            f.result()

    threads = [threading.Thread(target=caller, args=(t,))
               for t in range(n_threads)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    dt = time.perf_counter() - t0
    d_batches = engine.batches_dispatched - base_batches
    d_graphs = engine.graphs_dispatched - base_graphs
    sst = engine.scheduler.stats()
    print(f"[serve_gcn] concurrent: {n_threads} threads x {per_thread} "
          f"submits in {dt:.2f}s -> {d_batches} fused dispatches "
          f"({d_graphs / max(d_batches, 1):.1f} graphs/dispatch, "
          f"flushes: size={sst['flush_size']:.0f} "
          f"deadline={sst['flush_deadline']:.0f}, "
          f"p99 latency {sst['p99_latency_s'] * 1e3:.1f}ms)")

    # ---- streaming edge updates: mutate() + incremental plan repair ------
    # A batched edge delta against a LIVE graph: deletes a few edges,
    # inserts a few (with weights), and publishes the repaired plan as the
    # next version of g0's chain — reads in flight keep the old version.
    g0 = graphs[gid0]
    rng = np.random.default_rng(0)
    eids = rng.choice(g0.nnz, 8, replace=False)
    rows = rng.integers(0, g0.n_rows, 8)
    delta = EdgeDelta(
        delete_src=np.searchsorted(g0.rowptr, eids, side="right") - 1,
        delete_dst=g0.colidx[eids],
        insert_src=rows, insert_dst=rng.integers(0, g0.n_cols, 8),
        insert_val=rng.random(8).astype(np.float32),
        on_duplicate="replace", on_missing="ignore")
    info = engine.mutate(gid0, delta).result()   # Future, like submit()
    y = engine.submit(gid0, feats[gid0]).result()  # serves the NEW version
    g1 = delta.apply(g0)
    ref = GraphOp.build(g1, backend="blocked")(feats[gid0])
    merr = float(jnp.max(jnp.abs(y - ref)))
    assert merr < 1e-3, f"post-mutation mismatch: {merr}"
    print(f"[serve_gcn] mutate: v{info['version']} published via "
          f"{'repair' if info['repaired'] else 'rebuild'} "
          f"({info['dirty_rows']} dirty rows), post-delta max|err| = "
          f"{merr:.2e}  OK")
    engine.close()


if __name__ == "__main__":
    main()
