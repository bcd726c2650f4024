"""Versioned plan repair: delta semantics, chained keys, and the core
acceptance property — a repaired plan is SpMM-OUTPUT-identical to a full
rebuild of the post-delta graph, through both batched kernel backends.

``tests/conftest.py`` wires the ``hypothesis`` import to the real library
when installed and to the deterministic shim in ``tests/_compat``
otherwise, so the property tests run everywhere.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.core.graph import csr_apply_edge_delta, csr_from_edges, gcn_normalize
from repro.core.plan_cache import PartitionConfig, build_partition_plan
from repro.core.plan_repair import (EdgeDelta, apply_and_repair,
                                    delta_chain_hash, repair_plan)
from repro.kernels.ops import spmm_batched

from graph_helpers import make_powerlaw_csr

BACKENDS = ["blocked", "pallas"]

# a small bound so tiny test graphs cross the pattern/split boundary
SMALL_CFG = PartitionConfig(max_block_warps=4, max_warp_nzs=2)  # deg_bound 8


def _dense(g):
    a = np.zeros((g.n_rows, g.n_cols), np.float64)
    row = np.repeat(np.arange(g.n_rows), np.diff(g.rowptr))
    np.add.at(a, (row, g.colidx.astype(np.int64)), g.values.astype(np.float64))
    return a


def _spmm(plan, x, backend):
    """Kernel output re-ordered back to original rows (kernels emit in the
    plan's sorted-position order; ``inv_perm[row]`` is the row's position)."""
    y = spmm_batched([plan.slabs], [jnp.asarray(x, jnp.float32)],
                     [plan.n_rows], backend=backend)[0]
    return np.asarray(y)[np.asarray(plan.inv_perm)]


def _check_equivalent(pv, g_new, cfg):
    """The acceptance property: repaired plan == fresh build == dense, on
    every batched backend, for a random feature block."""
    x = np.random.default_rng(3).normal(size=(g_new.n_cols, 6))
    fresh = build_partition_plan(g_new, cfg)
    ref = _dense(g_new) @ x
    for backend in BACKENDS:
        got = _spmm(pv.plan, x, backend)
        np.testing.assert_allclose(got, ref, atol=1e-3, rtol=1e-3,
                                   err_msg=f"repair vs dense ({backend})")
        np.testing.assert_allclose(got, _spmm(fresh, x, backend),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"repair vs rebuild ({backend})")


def _graph(n=60, seed=0):
    return gcn_normalize(make_powerlaw_csr(n=n, seed=seed))


# --------------------------------------------------------- delta semantics

def test_delta_insert_delete_roundtrip():
    g = csr_from_edges(np.array([0, 1, 2]), np.array([1, 2, 0]), 8)
    delta = EdgeDelta(insert_src=[0, 1], insert_dst=[5, 7],
                      insert_val=[2.0, 3.0],
                      delete_src=[2], delete_dst=[0])
    g2 = delta.apply(g)
    d = _dense(g2) - _dense(g)
    assert d[0, 5] == pytest.approx(2.0)
    assert d[1, 7] == pytest.approx(3.0)
    assert d[2, 0] == pytest.approx(-1.0)
    assert g2.nnz == g.nnz + 1
    assert g.nnz == 3  # g untouched


def test_duplicate_insert_error_and_replace():
    g = csr_from_edges(np.array([0, 1]), np.array([1, 2]), 4)
    with pytest.raises(ValueError):
        csr_apply_edge_delta(g, insert_src=[0], insert_dst=[1])
    g2 = csr_apply_edge_delta(g, insert_src=[0], insert_dst=[1],
                              insert_val=[9.0], on_duplicate="replace")
    assert g2.nnz == g.nnz  # degree unchanged: value overwritten in place
    assert _dense(g2)[0, 1] == pytest.approx(9.0)
    # same (src, dst) twice in one insert list: last occurrence wins
    g3 = csr_apply_edge_delta(g, insert_src=[0, 0], insert_dst=[3, 3],
                              insert_val=[1.0, 7.0], on_duplicate="replace")
    assert _dense(g3)[0, 3] == pytest.approx(7.0)


def test_missing_delete_error_and_ignore():
    g = csr_from_edges(np.array([0, 1]), np.array([1, 2]), 4)
    with pytest.raises(ValueError):
        csr_apply_edge_delta(g, delete_src=[2], delete_dst=[3])
    g2 = csr_apply_edge_delta(g, delete_src=[2], delete_dst=[3],
                              on_missing="ignore")
    assert g2.nnz == g.nnz


def test_delete_removes_every_copy():
    # builders do not dedup: (0, 1) twice, one delete removes both copies
    g = csr_from_edges(np.array([0, 0, 1]), np.array([1, 1, 2]), 4)
    assert g.nnz == 3
    g2 = csr_apply_edge_delta(g, delete_src=[0], delete_dst=[1])
    assert g2.nnz == 1
    assert _dense(g2)[0, 1] == 0.0


def test_delta_range_validation():
    g = csr_from_edges(np.array([0]), np.array([1]), 4)
    with pytest.raises(ValueError):
        csr_apply_edge_delta(g, insert_src=[g.n_rows], insert_dst=[0])
    with pytest.raises(ValueError):
        csr_apply_edge_delta(g, insert_src=[0], insert_dst=[g.n_cols])
    with pytest.raises(ValueError):
        csr_apply_edge_delta(g, delete_src=[-1], delete_dst=[0])
    with pytest.raises(ValueError):
        EdgeDelta(insert_src=[0, 1], insert_dst=[2])  # length mismatch


# ------------------------------------------------------------ chained keys

def test_delta_chain_hash_deterministic_and_sensitive():
    d1 = EdgeDelta(insert_src=[0], insert_dst=[1])
    d1b = EdgeDelta(insert_src=[0], insert_dst=[1])
    d2 = EdgeDelta(insert_src=[0], insert_dst=[2])
    h = delta_chain_hash("parent", d1)
    assert h == delta_chain_hash("parent", d1b)   # same delta -> same key
    assert h != delta_chain_hash("parent", d2)    # different delta
    assert h != delta_chain_hash("other", d1)     # different parent
    assert h != "parent"
    # policy strings are part of the key (they change the transition)
    d1c = EdgeDelta(insert_src=[0], insert_dst=[1], on_duplicate="replace")
    assert h != delta_chain_hash("parent", d1c)


def test_repair_uses_chained_key_and_version_chain():
    g = _graph()
    plan = build_partition_plan(g, SMALL_CFG)
    delta = EdgeDelta(insert_src=[1], insert_dst=[2],
                      on_duplicate="replace")
    g2, pv = apply_and_repair(plan, g, delta)
    assert pv.version == plan.version + 1 == pv.plan.version
    assert pv.plan.graph_hash == delta_chain_hash(plan.graph_hash, delta)
    assert pv.plan.graph_hash != plan.graph_hash
    _check_equivalent(pv, g2, SMALL_CFG)


def test_empty_delta_advances_version_only():
    g = _graph()
    plan = build_partition_plan(g, SMALL_CFG)
    pv = repair_plan(plan, g, g, np.empty(0, np.int64), graph_hash="k2")
    assert pv.repaired and pv.version == plan.version + 1
    assert pv.plan.slabs["colidx"] is plan.slabs["colidx"]  # by reference


# ---------------------------------------------------- repair == rebuild

def test_repair_smoke_fixed_seed():
    """Fast CI smoke: one mixed delta, both backends, dense oracle."""
    g = _graph(n=80, seed=4)
    plan = build_partition_plan(g, SMALL_CFG)
    rng = np.random.default_rng(1)
    rows = rng.choice(g.n_rows, 6, replace=False)
    eids = rng.choice(g.nnz, 4, replace=False)
    delta = EdgeDelta(
        insert_src=rows, insert_dst=(rows * 3 + 1) % g.n_cols,
        insert_val=rng.normal(size=6).astype(np.float32),
        delete_src=np.searchsorted(g.rowptr, eids, side="right") - 1,
        delete_dst=g.colidx[eids],
        on_duplicate="replace", on_missing="ignore")
    g2, pv = apply_and_repair(plan, g, delta)
    assert pv.repaired
    _check_equivalent(pv, g2, SMALL_CFG)


def test_repair_row_crossing_deg_bound():
    """A row pushed across deg_bound moves between the pattern blocks and
    the split chunks; repair must re-emit it on the right side."""
    bound = SMALL_CFG.deg_bound
    n = 24
    src = np.repeat(np.arange(n), 3)
    dst = (src + np.tile(np.arange(1, 4), n)) % n   # row r: r+1, r+2, r+3
    g = csr_from_edges(src, dst, n)
    # grow row 5 to exactly the bound, then one past it
    plan = build_partition_plan(g, SMALL_CFG)
    up = EdgeDelta(insert_src=[5] * (bound - 3),
                   insert_dst=(5 + 4 + np.arange(bound - 3)) % n)
    g2, pv = apply_and_repair(plan, g, up)
    assert np.diff(g2.rowptr)[5] == bound
    _check_equivalent(pv, g2, SMALL_CFG)
    over = EdgeDelta(insert_src=[5], insert_dst=[(5 + bound + 2) % n])
    g3, pv2 = apply_and_repair(pv.plan, g2, over)
    assert np.diff(g3.rowptr)[5] > bound
    _check_equivalent(pv2, g3, SMALL_CFG)
    # and back down below the bound
    down = EdgeDelta(delete_src=[5] * 4,
                     delete_dst=g3.colidx[g3.rowptr[5]:g3.rowptr[5] + 4],
                     on_missing="ignore")
    g4, pv3 = apply_and_repair(pv2.plan, g3, down)
    _check_equivalent(pv3, g4, SMALL_CFG)


def test_repair_empties_and_refills_degree_bucket():
    """Deleting the only row of a degree class empties its bucket; a later
    insert refills it from a zero-degree row."""
    src = np.array([0, 0, 0, 1, 2])          # row 0 is the only deg-3 row
    g = csr_from_edges(src, np.array([1, 2, 3, 0, 1]), 4)
    plan = build_partition_plan(g, SMALL_CFG)
    wipe = EdgeDelta(delete_src=[0, 0, 0], delete_dst=[1, 2, 3])
    g2, pv = apply_and_repair(plan, g, wipe)
    assert np.diff(g2.rowptr)[0] == 0
    _check_equivalent(pv, g2, SMALL_CFG)
    refill = EdgeDelta(insert_src=[3, 3], insert_dst=[0, 2])  # deg-0 row 3
    g3, pv2 = apply_and_repair(pv.plan, g2, refill)
    _check_equivalent(pv2, g3, SMALL_CFG)


def test_churn_threshold_falls_back_to_rebuild():
    g = _graph(n=40)
    plan = build_partition_plan(g, SMALL_CFG)
    rows = np.arange(g.n_rows)               # touch every row
    delta = EdgeDelta(insert_src=rows, insert_dst=(rows + 1) % g.n_cols,
                      on_duplicate="replace")
    g2, pv = apply_and_repair(plan, g, delta, churn_threshold=0.25)
    assert not pv.repaired and "churn" in pv.reason
    assert pv.version == plan.version + 1
    _check_equivalent(pv, g2, SMALL_CFG)


def test_fragmentation_guard_recompacts_chained_repairs():
    """Every repair appends blocks; the guard must eventually trade the
    accumulated fragments for one full rebuild."""
    g = _graph(n=50, seed=2)
    plan = build_partition_plan(g, SMALL_CFG)
    saw_fragmentation_rebuild = False
    cur_g = g
    for step in range(80):
        r = int(np.random.default_rng(step).integers(0, g.n_rows))
        delta = EdgeDelta(insert_src=[r], insert_dst=[(r + step) % g.n_cols],
                          on_duplicate="replace")
        cur_g, pv = apply_and_repair(plan, cur_g, delta, churn_threshold=1.0)
        plan = pv.plan
        if not pv.repaired:
            assert "fragmentation" in pv.reason
            saw_fragmentation_rebuild = True
            break
    assert saw_fragmentation_rebuild, "guard never fired over 80 repairs"
    _check_equivalent(pv, cur_g, SMALL_CFG)


def test_repair_validates_inputs():
    g = _graph(n=30)
    plan = build_partition_plan(g, SMALL_CFG)
    with pytest.raises(ValueError):          # touched out of range
        repair_plan(plan, g, g, [g.n_rows], graph_hash="x")
    g_grown = csr_from_edges(np.array([0]), np.array([1]), g.n_rows + 1)
    with pytest.raises(ValueError):          # row count changed
        repair_plan(plan, g, g_grown, [0], graph_hash="x")
    g_other = csr_from_edges(np.array([0]), np.array([1]), g.n_rows)
    with pytest.raises(ValueError):          # plan built for other nnz
        repair_plan(plan, g_other, g_other, [0], graph_hash="x")


# ----------------------------------------------------- property (hypothesis)

@pytest.mark.slow
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       steps=st.integers(min_value=1, max_value=4),
       mode=st.sampled_from(["tpu", "paper"]))
def test_repair_chain_matches_rebuild_property(seed, steps, mode):
    """Random delta sequences over a power-law graph: after every step the
    repaired chain must agree with a dense oracle AND a fresh rebuild on
    both batched backends."""
    cfg = PartitionConfig(max_block_warps=4, max_warp_nzs=2, mode=mode)
    rng = np.random.default_rng(seed)
    g = gcn_normalize(make_powerlaw_csr(n=int(rng.integers(30, 90)),
                                        seed=seed))
    plan = build_partition_plan(g, cfg)
    for _ in range(steps):
        k_ins = int(rng.integers(0, 8))
        k_del = int(rng.integers(0, min(8, g.nnz)))
        eids = rng.choice(g.nnz, k_del, replace=False)
        delta = EdgeDelta(
            insert_src=rng.integers(0, g.n_rows, k_ins),
            insert_dst=rng.integers(0, g.n_cols, k_ins),
            insert_val=rng.normal(size=k_ins).astype(np.float32),
            delete_src=np.searchsorted(g.rowptr, eids, side="right") - 1,
            delete_dst=g.colidx[eids],
            on_duplicate="replace", on_missing="ignore")
        g, pv = apply_and_repair(plan, g, delta)
        assert pv.version == plan.version + 1
        plan = pv.plan
        _check_equivalent(pv, g, cfg)
