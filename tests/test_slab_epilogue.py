"""The slab kernels' shared epilogue, ``spmm_accel.scatter_block_rows``, and
the slab layout it relies on.

The epilogue reads each kept slab row once: every answer row by one gather
from a block that covers it, then local row 0 of every block by a
scatter-add that adds a split row's other partials. That is right only for
the layout ``pack_slabs`` gives and the batched merge and plan repair keep,
checked here after each: a block's live local rows are a prefix with
consecutive output rows, blocks of several rows cover disjoint rows, and a
split row is local row 0 of each of its blocks.

Against ``kernels/ref.py::slab_spmm_ref``, whose segment sum adds every slot
row: a row that no block splits comes out bit for bit the same (one term
each way); a split row may differ by the order of its partials.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import csr_from_edges, gcn_normalize
from repro.core.partition import slab_layout_ok
from repro.core.plan_cache import PartitionConfig, build_partition_plan
from repro.core.plan_repair import EdgeDelta, apply_and_repair
from repro.kernels.ref import slab_spmm_ref
from repro.kernels.spmm_accel import scatter_block_rows
from repro.kernels.spmm_batched import batch_graph_slabs, bucket_blocks
from repro.kernels.spmm_hbm import spmm_block_slabs_hbm
from repro.serve.graph_engine import GraphServeEngine

from graph_helpers import make_powerlaw_csr

SMALL_CFG = PartitionConfig(max_block_warps=4, max_warp_nzs=2)  # deg_bound 8


def assert_slab_layout(out_row, n_rows):
    """The layout ``scatter_block_rows`` folds: per block a prefix of live
    local rows at consecutive output rows; each output row in one slot, or
    split, at local row 0 of blocks of one row each."""
    out_row = np.asarray(out_row)
    live = out_row != n_rows
    n_b = live.sum(axis=1)
    assert np.array_equal(live, np.arange(out_row.shape[1]) < n_b[:, None])
    for b in np.flatnonzero(n_b):
        assert np.array_equal(out_row[b, :n_b[b]],
                              out_row[b, 0] + np.arange(n_b[b])), b
    slots = np.bincount(out_row[live], minlength=n_rows + 1)
    split = np.flatnonzero(slots > 1)
    holders = np.isin(out_row, split)
    assert not holders[:, 1:].any(), "a split row off local row 0"
    assert (n_b[holders[:, 0]] == 1).all(), "a split row beside others"
    assert slab_layout_ok(out_row, n_rows)


def _hub_graph(n=120, seed=0):
    """Power-law rows of up to 12 nonzeros plus three hubs of 40-70: at
    C = 8 each hub is split over 5-9 blocks, and rows over 8 over two; a
    quarter of the rows are empty."""
    rng = np.random.default_rng(seed)
    deg = np.minimum(rng.zipf(1.8, n), 12)
    deg[rng.choice(n, n // 4, replace=False)] = 0
    deg[[5, 50, 90]] = [40, 55, 70]
    src = np.repeat(np.arange(n), deg)
    return gcn_normalize(csr_from_edges(src, rng.integers(0, n, len(src)),
                                        n), add_self_loops=False)


def _plan_slabs(g, cfg=SMALL_CFG):
    plan = build_partition_plan(g, cfg)
    return {k: np.asarray(plan.slabs[k]) for k in
            ("colidx", "values", "rowloc", "out_row")}, plan.n_rows


def _case(name):
    """The slab arrays (colidx, values, rowloc, out_row) of one test case,
    and its n_rows."""
    rng = np.random.default_rng(len(name))
    if name in ("split_rows", "zero_degree_rows"):
        return _plan_slabs(_hub_graph(seed=len(name)))
    if name in ("dead_blocks", "merged_graphs"):
        graphs = ([_hub_graph(seed=1)] if name == "dead_blocks" else
                  [_hub_graph(seed=s) for s in (2, 3)]
                  + [gcn_normalize(make_powerlaw_csr(n=90, seed=4))])
        plans = [build_partition_plan(g, SMALL_CFG) for g in graphs]
        b = sum(p.num_blocks for p in plans)
        merged, _, _, n_out = batch_graph_slabs(
            [p.slabs for p in plans], [p.n_rows for p in plans],
            [p.n_cols for p in plans], pad_blocks_to=bucket_blocks(b + 1))
        assert merged["colidx"].shape[0] > b        # dead blocks at the end
        return {k: merged[k] for k in
                ("colidx", "values", "rowloc", "out_row")}, n_out
    if name == "shuffled_blocks":
        slabs, n = _plan_slabs(_hub_graph(seed=7))
        order = rng.permutation(slabs["colidx"].shape[0])
        return {k: v[order] for k, v in slabs.items()}, n
    raise ValueError(name)


def _split_rows(out_row, n_rows):
    counts = np.bincount(np.asarray(out_row)[:, 0], minlength=n_rows + 1)
    return counts[:n_rows] > 1


@pytest.mark.parametrize("case", ["split_rows", "zero_degree_rows",
                                  "dead_blocks", "merged_graphs",
                                  "shuffled_blocks", "hbm_3_heads"])
def test_epilogue_matches_the_segment_sum(case):
    if case == "hbm_3_heads":
        # a row of 601 nonzeros splits over three blocks of C = 256
        slabs, n = _plan_slabs(gcn_normalize(
            make_powerlaw_csr(n=300, seed=0, cap=600)), PartitionConfig())
        B, C = slabs["colidx"].shape
        live = (slabs["values"] != 0)[:, None]
        heads = np.random.default_rng(3).uniform(
            size=(B, 3, C)).astype(np.float32) * live
        x = np.random.default_rng(4).normal(size=(n, 3 * 128)).astype(
            np.float32)
        got = np.asarray(spmm_block_slabs_hbm(
            slabs["colidx"], heads, slabs["rowloc"], slabs["out_row"], x, n))
        want = np.concatenate([np.asarray(slab_spmm_ref(
            slabs["colidx"], heads[:, h], slabs["rowloc"], slabs["out_row"],
            x[:, h * 128:(h + 1) * 128], n)) for h in range(3)], axis=1)
        assert _split_rows(slabs["out_row"], n).any()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return
    slabs, n = _case(case)
    colidx, values, rowloc, out_row = (jnp.asarray(slabs[k]) for k in
                                       ("colidx", "values", "rowloc",
                                        "out_row"))
    R = out_row.shape[1]
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(int(colidx.max()) + 1, 24)).astype(np.float32))
    # the reference's own block rows, folded both ways
    onehot = (rowloc[..., None] == jnp.arange(R)).astype(jnp.float32)
    slab_out = jnp.einsum("bcr,bcf->brf", onehot,
                          values[..., None] * x[colidx])
    got = np.asarray(scatter_block_rows(
        jnp.pad(slab_out, ((0, 0), (0, 0), (0, 8))), out_row, n, 24))
    want = np.asarray(slab_spmm_ref(colidx, values, rowloc, out_row, x, n))
    split = _split_rows(out_row, n)
    assert got.shape == want.shape == (n, 24)
    assert np.array_equal(got[~split], want[~split])
    np.testing.assert_allclose(got[split], want[split], rtol=1e-6,
                               atol=1e-6)
    if case in ("split_rows", "merged_graphs"):
        assert split.sum() >= 3
    if case == "zero_degree_rows":
        assert (want == 0).all(axis=1).sum() >= n // 5


def test_layout_after_pack_slabs():
    for seed in range(4):
        g = _hub_graph(seed=seed)
        plan = build_partition_plan(g, SMALL_CFG)
        assert_slab_layout(plan.slabs["out_row"], plan.n_rows)
        assert _split_rows(plan.slabs["out_row"], plan.n_rows).sum() >= 3


def test_layout_after_batch_graph_slabs():
    plans = [build_partition_plan(_hub_graph(seed=s), SMALL_CFG)
             for s in range(3)]
    plans.append(build_partition_plan(
        gcn_normalize(make_powerlaw_csr(n=50, seed=9)), PartitionConfig()))
    merged, _, _, n_out = batch_graph_slabs(
        [p.slabs for p in plans], [p.n_rows for p in plans],
        [p.n_cols for p in plans], pad_blocks_to=512)
    assert merged["out_row"].shape == (512, int(merged["R"]))
    assert_slab_layout(merged["out_row"], n_out)


def test_layout_after_plan_repair_splices_a_dirty_range():
    """A chain of repairs: a row grown past the block capacity, rows emptied,
    an empty row filled, scattered edits; the layout holds after each, and
    the repaired plan still answers as the graph's dense product does."""
    g = _hub_graph(seed=12)
    plan = build_partition_plan(g, SMALL_CFG)
    deg = np.diff(g.rowptr)
    empty = int(np.flatnonzero(deg == 0)[0])
    small = int(np.flatnonzero((deg > 0) & (deg < 4))[0])
    full = np.flatnonzero(deg > 0)
    deltas = [
        EdgeDelta(insert_src=[small] * 12, insert_dst=np.arange(12) + 1,
                  on_duplicate="replace"),
        EdgeDelta(delete_src=np.repeat(full[:2], 1),
                  delete_dst=g.colidx[g.rowptr[full[:2]]],
                  on_missing="ignore"),
        EdgeDelta(insert_src=[empty, empty], insert_dst=[0, 3]),
        EdgeDelta(insert_src=full[::9], insert_dst=full[::9] % 7,
                  on_duplicate="replace"),
    ]
    x = np.random.default_rng(13).normal(size=(g.n_cols, 5)).astype(
        np.float32)
    for delta in deltas:
        g, pv = apply_and_repair(plan, g, delta)
        assert pv.repaired, pv.reason
        plan = pv.plan
        assert_slab_layout(plan.slabs["out_row"], plan.n_rows)
        got = np.asarray(slab_spmm_ref(
            plan.slabs["colidx"], plan.slabs["values"], plan.slabs["rowloc"],
            plan.slabs["out_row"], jnp.asarray(x), plan.n_rows))
        dense = np.zeros((g.n_rows, g.n_cols))
        np.add.at(dense, (np.repeat(np.arange(g.n_rows), np.diff(g.rowptr)),
                          g.colidx), g.values)
        np.testing.assert_allclose(got[np.asarray(plan.inv_perm)], dense @ x,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fault", ["hole", "scattered", "overlap",
                                   "split_off_row_0", "split_beside_others",
                                   "out_of_range"])
def test_layout_check_refuses_what_the_fold_cannot_read(fault):
    """``partition.slab_layout_ok`` (run on every plan the cache reloads
    from disk) refuses each way a block can leave the folded layout."""
    n = 20
    out_row = np.array([[0, 1, 2, n], [3, 4, n, n], [5, n, n, n],
                        [5, n, n, n], [n, n, n, n]], np.int32)
    assert slab_layout_ok(out_row, n)
    bad = out_row.copy()
    if fault == "hole":                  # a masked slot inside a block
        bad[0, 1] = n
    elif fault == "scattered":           # rows not at consecutive positions
        bad[1, 1] = 9
    elif fault == "overlap":             # two blocks of several rows share 4
        bad[4, :2] = [4, 5]
    elif fault == "split_off_row_0":     # split row 5 also at local row 1
        bad[1, 2] = 5
    elif fault == "split_beside_others":  # split row 5 shares a block
        bad[2, 1] = 6
    elif fault == "out_of_range":
        bad[4, 0] = n + 1
    assert not slab_layout_ok(bad, n)


def test_repair_masks_from_the_first_touched_row():
    """A touched row off local row 0 keeps the rows before it in place; it
    and the rows after it are re-emitted, and ``dirty_rows`` counts them."""
    g = _hub_graph(seed=12)
    plan = build_partition_plan(g, SMALL_CFG)
    out_row = np.asarray(plan.slabs["out_row"])
    n, R = plan.n_rows, out_row.shape[1]
    n_b = (out_row != n).sum(axis=1)
    b = int(np.flatnonzero(n_b >= 3)[0])
    row = int(np.flatnonzero(np.asarray(plan.inv_perm) == out_row[b, 1])[0])
    _, pv = apply_and_repair(plan, g, EdgeDelta(
        insert_src=[row], insert_dst=[(row + 1) % n], on_duplicate="replace"))
    assert pv.repaired, pv.reason
    kept = np.asarray(pv.plan.slabs["out_row"])[b]
    np.testing.assert_array_equal(kept, np.r_[out_row[b, :1], [n] * (R - 1)])
    assert pv.dirty_rows == n_b[b] - 1
    assert_slab_layout(pv.plan.slabs["out_row"], n)


def test_repair_churn_threshold_reads_the_re_emitted_rows():
    """One touched row at local row 0 of a full block re-emits the whole
    block; a threshold above one row but below the block falls back."""
    g = _hub_graph(seed=12)
    plan = build_partition_plan(g, SMALL_CFG)
    out_row = np.asarray(plan.slabs["out_row"])
    n = plan.n_rows
    n_b = (out_row != n).sum(axis=1)
    b = int(np.argmax(n_b))
    assert n_b[b] >= 3
    row = int(np.flatnonzero(np.asarray(plan.inv_perm) == out_row[b, 0])[0])
    delta = EdgeDelta(insert_src=[row], insert_dst=[(row + 1) % n],
                      on_duplicate="replace")
    _, pv = apply_and_repair(plan, g, delta, churn_threshold=2.5 / n)
    assert not pv.repaired and "churn" in pv.reason, pv.reason
    _, pv = apply_and_repair(plan, g, delta,
                             churn_threshold=(n_b[b] + 0.5) / n)
    assert pv.repaired and pv.dirty_rows == n_b[b], pv.reason


def test_epilogue_rows_counts_answer_rows_and_blocks():
    """``epilogue_rows`` grows by n_out + B a dispatch: the answer rows and
    one row per merged, bucket-padded block."""
    g = _hub_graph(seed=14)
    engine = GraphServeEngine(backend="blocked")
    try:
        engine.register_graph("g", g)
        x = np.ones((g.n_cols, 4), np.float32)
        engine.submit("g", x).result()
        s = engine.stats()
    finally:
        engine.close()
    assert s["batches_dispatched"] == 1
    assert s["epilogue_rows"] == g.n_rows + s["padded_blocks"]
    assert s["padded_blocks"] == bucket_blocks(s["live_blocks"])
