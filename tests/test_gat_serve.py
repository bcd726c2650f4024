"""Graph attention requests through ``GraphServeEngine.submit(...,
attention=(el, er))``: the scheduler, plan cache, router, per-call values
(``kernels/edge_softmax.py``) and each slab kernel, against
``attention_spmm_ref``.

Tolerance: each answer row is a convex combination (the softmax weights sum
to 1) of ``z`` rows of unit scale, computed in f32 in another summation order
than the oracle's, and the oracle's own exp and division round too; 2e-5
absolute is about 100 f32 ulps of the unit-scale answers and far below what a
wrongly normalised row, a dropped or counted padding slot, or a swapped head
moves them (tenths).
"""
import numpy as np
import pytest

from repro.core.graph import CSRGraph, csr_from_edges, gcn_normalize
from repro.kernels.ref import attention_spmm_ref, csr_spmm_ref
from repro.serve.fleet import FleetGraphEngine
from repro.serve.graph_engine import GraphServeEngine

from graph_helpers import make_powerlaw_csr

ATOL = 2e-5
BACKENDS = {"resident": "pallas", "windowed": "windowed", "hbm": "hbm",
            "blocked": "blocked"}
HOLD_MS = 200.0   # long enough that back-to-back submits share one flush


def _split_row_graph():
    """A power-law graph whose longest row (601 nonzeros) is longer than a
    block's 256 slots, so it is split over three blocks."""
    g = make_powerlaw_csr(n=300, seed=0, cap=600)
    assert np.diff(g.rowptr).max() > 256
    return g


def _padded_graph():
    """Every row holds 3 nonzeros: a block packs 64 rows into 192 of its
    256 slots, so a quarter of every block is padding."""
    n = 200
    rng = np.random.default_rng(1)
    src = np.repeat(np.arange(n), 3)
    return csr_from_edges(src, rng.integers(0, n, len(src)), n)


def _inputs(g, heads, f_head, seed):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(g.n_cols, heads, f_head)).astype(np.float32)
    el = rng.normal(size=(g.n_cols, heads)).astype(np.float32)
    er = rng.normal(size=(g.n_rows, heads)).astype(np.float32)
    return z, el, er


def _oracle(g, z, el, er, slope):
    return np.asarray(attention_spmm_ref(g.rowptr, g.colidx, z, el, er,
                                         slope))


def _submit_together(engine, calls):
    """Submit every ``(args, kwargs)`` back to back: one flush takes them."""
    futures = [engine.submit(*a, **kw) for a, kw in calls]
    return [np.asarray(f.result()) for f in futures]


def case_split_row(engine):
    g = _split_row_graph()
    engine.register_graph("g", g)
    z, el, er = _inputs(g, 2, 20, seed=2)
    (out,) = _submit_together(engine, [(("g", z), {"attention": (el, er)})])
    return [(out, _oracle(g, z, el, er, 0.2))]


def case_padding_slots(engine):
    g = _padded_graph()
    engine.register_graph("g", g)
    z, el, er = _inputs(g, 2, 20, seed=3)
    (out,) = _submit_together(engine, [(("g", z), {
        "attention": (el, er), "negative_slope": 0.01})])
    return [(out, _oracle(g, z, el, er, 0.01))]


def case_heads_of_250(engine):
    g = gcn_normalize(make_powerlaw_csr(n=160, seed=4))
    engine.register_graph("g", g)
    z, el, er = _inputs(g, 3, 250, seed=4)
    (out,) = _submit_together(engine, [(("g", z), {"attention": (el, er)})])
    return [(out, _oracle(g, z, el, er, 0.2))]


def case_three_heads_of_40(engine):
    """The last GAT layer's shape: 3 heads of 40 columns, each padded to one
    128-lane tile."""
    g = _split_row_graph()
    engine.register_graph("g", g)
    z, el, er = _inputs(g, 3, 40, seed=5)
    (out,) = _submit_together(engine, [(("g", z), {"attention": (el, er)})])
    return [(out, _oracle(g, z, el, er, 0.2))]


def case_two_requests_fuse(engine):
    """Two attention requests of 2 x 20 and 1 x 40, slopes 0.2 and 0.05, in
    one flush: one dispatch of three heads."""
    g = _split_row_graph()
    engine.register_graph("g", g)
    z1, el1, er1 = _inputs(g, 2, 20, seed=6)
    z2, el2, er2 = _inputs(g, 1, 40, seed=7)
    out1, out2 = _submit_together(engine, [
        (("g", z1), {"attention": (el1, er1)}),
        (("g", z2), {"attention": (el2, er2), "negative_slope": 0.05})])
    stats = engine.stats()
    assert stats["batches_dispatched"] == 1 and stats["attn_heads"] == 3
    return [(out1, _oracle(g, z1, el1, er1, 0.2)),
            (out2, _oracle(g, z2, el2, er2, 0.05))]


def case_attention_beside_plain(engine):
    """An attention request and two plain requests on one graph in one
    flush: the plain ones are one more head, with the plan's values."""
    g = gcn_normalize(_split_row_graph())
    engine.register_graph("g", g)
    z, el, er = _inputs(g, 2, 20, seed=8)
    rng = np.random.default_rng(9)
    x1 = rng.normal(size=(g.n_cols, 8)).astype(np.float32)
    x2 = rng.normal(size=(g.n_cols, 5)).astype(np.float32)
    out, y1, y2 = _submit_together(engine, [
        (("g", z), {"attention": (el, er)}), (("g", x1), {}),
        (("g", x2), {})])
    stats = engine.stats()
    assert stats["batches_dispatched"] == 1 and stats["attn_heads"] == 2
    return [(out, _oracle(g, z, el, er, 0.2))] + [
        (y, np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, x)))
        for y, x in ((y1, x1), (y2, x2))]


CASES = {f.__name__[5:]: f for f in (
    case_split_row, case_padding_slots, case_heads_of_250,
    case_three_heads_of_40, case_two_requests_fuse,
    case_attention_beside_plain)}


@pytest.mark.parametrize("kernel", sorted(BACKENDS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_attention_matches_oracle(case, kernel):
    engine = GraphServeEngine(backend=BACKENDS[kernel], max_wait_ms=HOLD_MS)
    try:
        pairs = CASES[case](engine)
        stats = engine.stats()
    finally:
        engine.close()
    if kernel != "blocked":
        assert stats[f"routed_{kernel}"] == stats["batches_dispatched"] == 1
    assert stats["attn_dispatches"] == 1
    assert stats["dispatch_scores_s"] > 0
    for got, want in pairs:
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_padding_slots_get_exactly_zero_and_rows_sum_to_one():
    """The per-call values themselves: each live row's weights sum to 1 per
    head, over all the blocks of a split row, and padding slots hold 0."""
    from repro.kernels.edge_softmax import ATTENTION, edge_softmax_slabs
    from repro.core.plan_cache import PartitionConfig, build_partition_plan
    g = _split_row_graph()
    plan = build_partition_plan(g, PartitionConfig())
    z, el, er = _inputs(g, 2, 1, seed=10)
    slabs = plan.slabs
    B = plan.num_blocks
    nnz = plan.partition.nnz_blk
    er_sorted = np.zeros((g.n_rows + 1, 2), np.float32)
    er_sorted[np.asarray(plan.inv_perm)] = er
    vals = np.asarray(edge_softmax_slabs(
        slabs["colidx"], slabs["values"], slabs["rowloc"], slabs["out_row"],
        nnz.astype(np.int32), np.full((B, 2), ATTENTION, np.int32),
        np.full((B, 2), 0.2, np.float32), el, er_sorted))
    live = np.arange(slabs["C"])[None, :] < nnz[:, None]
    assert np.all(vals[~np.broadcast_to(live[:, None], vals.shape)] == 0.0)
    rows = np.take_along_axis(np.asarray(slabs["out_row"]),
                              np.asarray(slabs["rowloc"]), axis=1)
    sums = np.zeros((g.n_rows, 2))
    for h in range(2):
        np.add.at(sums[:, h], rows[live], vals[:, h][live])
    has_row = np.diff(g.rowptr)[np.argsort(np.asarray(plan.inv_perm))] > 0
    np.testing.assert_allclose(sums[has_row], 1.0, atol=1e-6)


def test_gcn_request_unchanged_bit_for_bit():
    """A plain request beside the attention code still answers exactly what
    the CSR oracle does: on integer-valued data every f32 sum is exact, so
    any value or head mixed into the plain path would show."""
    rng = np.random.default_rng(11)
    base = _split_row_graph()
    g = CSRGraph(base.rowptr, base.colidx,
                 rng.integers(1, 4, base.nnz).astype(np.float32), base.n_cols)
    x = rng.integers(-3, 4, (g.n_cols, 24)).astype(np.float32)
    want = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, x))
    for backend in ("pallas", "windowed", "hbm"):
        engine = GraphServeEngine(backend=backend)
        try:
            engine.register_graph("g", g)
            got = np.asarray(engine.submit("g", x).result())
            assert engine.stats()["attn_dispatches"] == 0
        finally:
            engine.close()
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bad", ["fleet", "el_shape", "er_shape",
                                 "z_rows", "flat_features", "slope"])
def test_attention_request_refused(bad):
    """Malformed attention requests raise at ``submit``; the fleet engine
    refuses attention outright rather than serve it as a plain
    aggregation."""
    g = _padded_graph()
    z, el, er = _inputs(g, 2, 20, seed=12)
    engine = (FleetGraphEngine(backend="blocked", n_devices=1)
              if bad == "fleet" else GraphServeEngine(backend="blocked"))
    try:
        engine.register_graph("g", g)
        kw = {"attention": (el, er)}
        x = z
        if bad == "el_shape":
            kw["attention"] = (el[:, :1], er)
        elif bad == "er_shape":
            kw["attention"] = (el, er[:-1])
        elif bad == "z_rows":
            x = z[:-1]
        elif bad == "flat_features":
            x = z.reshape(g.n_cols, 40)
        elif bad == "slope":
            kw["negative_slope"] = float("nan")
        with pytest.raises(ValueError):
            engine.submit("g", x, **kw)
        assert engine.stats()["requests_served"] == 0
    finally:
        engine.close()
