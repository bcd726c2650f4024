"""The serving mutation path: ``mutate()`` end to end through the engine.

Covers the versioned-lifecycle acceptance criteria:

* a mutation publishes a new plan version and later reads observe it;
* mutations racing live read traffic never fail a read, never block it,
  and never tear it — every answer equals the SpMM of SOME version in
  the published chain (pre- or post-publish, never a mix);
* version pins and retired plans drain to zero once traffic stops;
* a bad delta fails only its own mutation future, not the flush's reads;
* the multihost engine converges: a mutation on one host broadcasts the
  delta sequence, both hosts end at the same (key, version), and both
  serve the post-delta graph (in-process two-host fixture — real peer
  TCP, no subprocesses).
"""
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.graph import gcn_normalize
from repro.core.plan_repair import EdgeDelta
from repro.serve.graph_engine import GraphServeEngine

from graph_helpers import make_powerlaw_csr


def _dense(g):
    a = np.zeros((g.n_rows, g.n_cols), np.float64)
    row = np.repeat(np.arange(g.n_rows), np.diff(g.rowptr))
    np.add.at(a, (row, g.colidx.astype(np.int64)), g.values.astype(np.float64))
    return a


def _delta(g, seed, k=3):
    """A small mixed delta valid against ``g``."""
    rng = np.random.default_rng(seed)
    eids = rng.choice(g.nnz, k, replace=False)
    rows = rng.integers(0, g.n_rows, k)
    return EdgeDelta(
        insert_src=rows, insert_dst=rng.integers(0, g.n_cols, k),
        insert_val=rng.normal(size=k).astype(np.float32),
        delete_src=np.searchsorted(g.rowptr, eids, side="right") - 1,
        delete_dst=g.colidx[eids],
        on_duplicate="replace", on_missing="ignore")


def _drain(engine, timeout=5.0):
    """Poll until version pins and retired plans drain (a resolved future
    only means the answer is out — the flush thread's finally-unpin can
    still be in flight for a moment)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        s = engine.stats()
        if s["cache_pins"] == 0 and s["cache_retired_live"] == 0:
            return s
        time.sleep(0.01)
    raise AssertionError(f"pins/retired never drained: {engine.stats()}")


def test_mutate_publishes_and_serves_new_version():
    engine = GraphServeEngine(backend="blocked")
    g0 = gcn_normalize(make_powerlaw_csr(n=70, seed=0))
    engine.register_graph("g", g0)
    assert engine.graph_version("g") == 0

    delta = _delta(g0, seed=1)
    g1 = delta.apply(g0)
    info = engine.mutate("g", delta).result(timeout=30)
    assert info["version"] == 1 and info["graph_id"] == "g"
    assert engine.graph_version("g") == 1
    assert engine.mutations_applied == 1
    assert engine.plan_repairs + engine.plan_rebuilds == 1

    x = np.random.default_rng(2).normal(size=(g1.n_cols, 5))
    out = engine.submit("g", jnp.asarray(x, jnp.float32)).result(timeout=30)
    np.testing.assert_allclose(np.asarray(out), _dense(g1) @ x,
                               atol=1e-3, rtol=1e-3)
    _drain(engine)
    engine.close()


def test_sequential_mutations_chain_versions():
    engine = GraphServeEngine(backend="blocked")
    g = gcn_normalize(make_powerlaw_csr(n=60, seed=3))
    engine.register_graph("g", g)
    for i in range(4):
        delta = _delta(g, seed=10 + i)
        g = delta.apply(g)
        info = engine.mutate("g", delta).result(timeout=30)
        assert info["version"] == i + 1
    x = np.random.default_rng(0).normal(size=(g.n_cols, 4))
    out = engine.submit("g", jnp.asarray(x, jnp.float32)).result(timeout=30)
    np.testing.assert_allclose(np.asarray(out), _dense(g) @ x,
                               atol=1e-3, rtol=1e-3)
    _drain(engine)
    engine.close()


def test_bad_delta_fails_only_its_mutation():
    engine = GraphServeEngine(backend="blocked")
    g = gcn_normalize(make_powerlaw_csr(n=50, seed=5))
    engine.register_graph("g", g)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(g.n_cols, 3)),
                    jnp.float32)
    # a delete of a non-existent edge with strict on_missing: the delta is
    # well-formed at submit time, fails during apply
    bad = EdgeDelta(delete_src=[0], delete_dst=[g.n_cols - 1])
    assert _dense(g)[0, g.n_cols - 1] == 0.0
    read = engine.submit("g", x)
    fut = engine.mutate("g", bad)
    with pytest.raises(ValueError):
        fut.result(timeout=30)
    # the read (same flush or not) is unaffected, version unchanged
    np.testing.assert_allclose(np.asarray(read.result(timeout=30)),
                               _dense(g) @ np.asarray(x), atol=1e-3,
                               rtol=1e-3)
    assert engine.graph_version("g") == 0
    _drain(engine)
    engine.close()


@pytest.mark.slow
def test_mutations_racing_reads_are_never_torn():
    """The hammer: reader threads submit continuously while a writer
    publishes a chain of versions. Every answer must equal the SpMM of
    some published version — pre- or post-publish, never a blend — and
    no read may ever fail."""
    engine = GraphServeEngine(backend="blocked", max_wait_ms=1.0)
    g0 = gcn_normalize(make_powerlaw_csr(n=80, seed=7))
    engine.register_graph("g", g0)

    n_versions = 5
    chain = [g0]
    for i in range(n_versions):
        chain.append(_delta(chain[-1], seed=100 + i).apply(chain[-1]))
    x = np.random.default_rng(9).normal(size=(g0.n_cols, 4))
    refs = [_dense(g) @ x for g in chain]
    xj = jnp.asarray(x, jnp.float32)

    stop = threading.Event()
    failures = []
    matched_versions = set()

    def reader():
        while not stop.is_set():
            try:
                y = np.asarray(engine.submit("g", xj).result(timeout=30))
            except Exception as e:  # noqa: BLE001 — a failed read IS the bug
                failures.append(repr(e))
                return
            errs = [float(np.max(np.abs(y - r))) for r in refs]
            best = int(np.argmin(errs))
            if errs[best] > 1e-3:
                failures.append(f"answer matches no version: errs={errs}")
                return
            matched_versions.add(best)

    readers = [threading.Thread(target=reader) for _ in range(3)]
    for t in readers:
        t.start()
    try:
        for i in range(n_versions):
            delta = _delta(chain[i], seed=100 + i)
            info = engine.mutate("g", delta).result(timeout=30)
            assert info["version"] == i + 1
            time.sleep(0.02)     # let readers overlap each published version
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=30)
    assert not failures, failures[:3]
    # the race was real: reads landed on several distinct versions
    assert len(matched_versions) >= 2, matched_versions
    assert engine.graph_version("g") == n_versions
    s = _drain(engine)
    assert s["cache_publishes"] >= n_versions
    engine.close()


# ----------------------------------------------------------- multihost

def _two_host_engines():
    from repro.distributed.multihost import MultihostContext, PeerClient
    from repro.serve.fleet import MultihostGraphEngine

    devs = list(jax.local_devices())

    def ctx(i):
        return MultihostContext(process_index=i, process_count=2,
                                coordinator=None, local_devices=devs,
                                global_devices=devs)

    a = MultihostGraphEngine(context=ctx(0), serve_port=0,
                             peer_addresses={}, backend="blocked")
    b = MultihostGraphEngine(context=ctx(1), serve_port=0,
                             peer_addresses={}, backend="blocked")
    a.peers = {1: PeerClient(("127.0.0.1", b.server.port),
                             process_index=0, epoch=0)}
    b.peers = {0: PeerClient(("127.0.0.1", a.server.port),
                             process_index=1, epoch=0)}
    a.connect_peers()
    b.connect_peers()
    return a, b


@pytest.mark.slow
def test_multihost_mutation_converges_both_hosts():
    a, b = _two_host_engines()
    try:
        rng = np.random.default_rng(0)
        pool = {}
        for i in range(6):   # enough graphs that consistent hashing puts
            gid = f"g{i}"    # at least one on each host
            g = gcn_normalize(make_powerlaw_csr(n=50 + 10 * i, seed=i))
            pool[gid] = g
            a.register_graph(gid, g)
            b.register_graph(gid, g)
        all_owners = {gid: a.directory.place(a._keys[gid]).host
                      for gid in pool}
        assert set(all_owners.values()) == {0, 1}, all_owners
        # mutate one graph per owning host: exercises both the owner-repair
        # path (a owns it) and the non-owner rebind path (b owns it)
        picks = {h: next(g for g, o in all_owners.items() if o == h)
                 for h in (0, 1)}
        graphs = {gid: pool[gid] for gid in picks.values()}
        owners = {gid: all_owners[gid] for gid in graphs}

        # single writer (host a) mutates BOTH graphs — one it owns, one
        # owned by the peer — exercising owner-repair and non-owner rebind
        for gid, g in list(graphs.items()):
            delta = _delta(g, seed=42)
            graphs[gid] = delta.apply(g)
            info = a.mutate(gid, delta).result(timeout=60)
            assert info["version"] == 1

        for gid in graphs:
            # identical chained key and version on both hosts
            assert a._keys[gid] == b._keys[gid]
            assert a._versions[gid] == b._versions[gid] == 1
            assert a.directory.place(a._keys[gid]).host == owners[gid]
        assert a.mutation_broadcasts == 2
        assert b.remote_mutations == 2
        assert b.mutation_broadcast_failures == 0

        # both hosts serve the POST-delta graphs (forwarding included)
        for eng in (a, b):
            for gid, g in graphs.items():
                x = rng.normal(size=(g.n_cols, 4))
                out = eng.submit(gid, jnp.asarray(x, jnp.float32)).result(
                    timeout=60)
                np.testing.assert_allclose(np.asarray(out), _dense(g) @ x,
                                           atol=1e-3, rtol=1e-3)
    finally:
        a.close()
        b.close()


@pytest.mark.slow
def test_multihost_version_fork_guard():
    """Two writers racing the same graph must not silently diverge: a
    replayed broadcast against the wrong base version raises."""
    a, b = _two_host_engines()
    try:
        g = gcn_normalize(make_powerlaw_csr(n=50, seed=1))
        a.register_graph("g", g)
        b.register_graph("g", g)
        delta = _delta(g, seed=3)
        a.mutate("g", delta).result(timeout=60)   # both hosts now at v1
        with pytest.raises(RuntimeError, match="fork"):
            b._apply_deltas_local("g", [_delta(delta.apply(g), seed=4)],
                                  expect_base=0)  # stale writer base
    finally:
        a.close()
        b.close()
