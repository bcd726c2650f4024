"""Batched multi-graph SpMM == per-graph SpMM, including nasty edge cases."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import csr_from_edges, gcn_normalize
from repro.core.plan_cache import PartitionConfig, build_partition_plan
from repro.kernels.ref import csr_spmm_ref
from repro.kernels.spmm_accel import spmm_block_slabs
from repro.kernels.spmm_batched import batch_graph_slabs, bucket_blocks, spmm_batched

from graph_helpers import make_powerlaw_csr, make_wide_csr


def _plan_x(g, cfg, F, seed):
    plan = build_partition_plan(g, cfg)
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(g.n_rows, F)),
                    dtype=jnp.float32)
    return plan, x


def _check_parity(plans, xs, backend, **kw):
    outs = spmm_batched([p.slabs for p in plans], xs,
                        [p.n_rows for p in plans], backend=backend, **kw)
    assert len(outs) == len(plans)
    for p, x, out in zip(plans, xs, outs):
        ref = spmm_block_slabs(p.slabs["colidx"], p.slabs["values"],
                               p.slabs["rowloc"], p.slabs["out_row"],
                               x, p.n_rows)
        assert out.shape == (p.n_rows, x.shape[1])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["pallas", "blocked"])
def test_batched_matches_individual(backend):
    cfg = PartitionConfig()
    plans, xs = [], []
    for i, (n, F) in enumerate([(150, 32), (90, 64), (220, 16)]):
        g = gcn_normalize(make_powerlaw_csr(n=n, seed=i, zipf=1.8))
        p, x = _plan_x(g, cfg, F, seed=i)
        plans.append(p)
        xs.append(x)
    _check_parity(plans, xs, backend)


def test_batched_single_graph_degenerate():
    cfg = PartitionConfig()
    g = gcn_normalize(make_powerlaw_csr(n=77, seed=4))
    p, x = _plan_x(g, cfg, 40, seed=4)
    _check_parity([p], [x], "blocked")


@pytest.mark.slow
def test_batched_mixed_partition_configs():
    """Graphs partitioned under different configs (different C, R) pad to a
    common capacity and still agree with their own single-graph runs."""
    cfgs = [PartitionConfig(),                                     # C=256
            PartitionConfig(max_block_warps=8, max_warp_nzs=4),    # C=32
            PartitionConfig(mode="paper", max_block_warps=12,
                            max_warp_nzs=8)]                       # C=96
    plans, xs = [], []
    for i, cfg in enumerate(cfgs):
        g = gcn_normalize(make_powerlaw_csr(n=100 + 30 * i, seed=i))
        p, x = _plan_x(g, cfg, 24, seed=10 + i)
        plans.append(p)
        xs.append(x)
    assert len({p.slabs["C"] for p in plans}) > 1, "test needs mixed C"
    _check_parity(plans, xs, "pallas")


def test_batched_zero_degree_rows():
    """Rows with no non-zeros must come back exactly zero, per graph."""
    # graph 0: rows 0,2,4.. empty; graph 1: dense-ish power law
    src = np.array([1, 1, 3, 5, 5, 5], dtype=np.int64)
    dst = np.array([0, 2, 1, 4, 5, 0], dtype=np.int64)
    g0 = csr_from_edges(src, dst, 7)
    g1 = gcn_normalize(make_powerlaw_csr(n=60, seed=3))
    cfg = PartitionConfig(max_block_warps=8, max_warp_nzs=4)
    p0, x0 = _plan_x(g0, cfg, 8, seed=0)
    p1, x1 = _plan_x(g1, cfg, 8, seed=1)
    _check_parity([p0, p1], [x0, x1], "blocked")
    outs = spmm_batched([p0.slabs, p1.slabs], [x0, x1],
                        [p0.n_rows, p1.n_rows], backend="blocked")
    # zero-degree rows of g0 are zero in DEGREE-SORTED order: empty rows sort
    # first, and g0 has 4 of them (0, 2, 4, 6)
    np.testing.assert_array_equal(np.asarray(outs[0][:4]), 0.0)


@pytest.mark.slow
def test_batched_split_rows_degree_exceeds_capacity():
    """Rows with degree > C split across blocks; cross-block accumulation in
    the fused epilogue must not leak between graphs."""
    cfg = PartitionConfig(max_block_warps=4, max_warp_nzs=4)  # C = 16
    plans, xs, graphs = [], [], []
    for i in range(2):
        g = gcn_normalize(make_powerlaw_csr(n=50, seed=20 + i, zipf=1.3))
        assert (np.diff(g.rowptr) >= 16).any(), "need at least one split row"
        p, x = _plan_x(g, cfg, 12, seed=20 + i)
        plans.append(p)
        xs.append(x)
        graphs.append(g)
    _check_parity(plans, xs, "pallas")
    # also against the layout-free oracle (un-permute to ORIGINAL row order)
    outs = spmm_batched([p.slabs for p in plans], xs,
                        [p.n_rows for p in plans], backend="pallas")
    for g, p, x, out in zip(graphs, plans, xs, outs):
        ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values,
                                      np.asarray(x)))
        np.testing.assert_allclose(np.asarray(out[p.inv_perm]), ref,
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.slow
@pytest.mark.parametrize("min_bucket", [64, 256])
def test_block_bucketing_parity(min_bucket):
    cfg = PartitionConfig()
    plans, xs = [], []
    for i in range(3):
        g = gcn_normalize(make_powerlaw_csr(n=80 + 40 * i, seed=30 + i))
        p, x = _plan_x(g, cfg, 16, seed=30 + i)
        plans.append(p)
        xs.append(x)
    b_total = sum(p.num_blocks for p in plans)
    bucket = bucket_blocks(b_total, min_bucket)
    assert bucket >= b_total and bucket >= min_bucket
    _check_parity(plans, xs, "blocked", pad_blocks_to=bucket)


def test_bucket_blocks_tiers_bound_padding_waste():
    """Power-of-two tiers from 8: a tiny batch no longer pads to 256
    blocks, and waste stays below 2x for any batch at least one tier big."""
    assert bucket_blocks(3) == 8
    assert bucket_blocks(8) == 8
    assert bucket_blocks(9) == 16
    assert bucket_blocks(100) == 128
    for b in range(8, 2000, 37):
        bucket = bucket_blocks(b)
        assert b <= bucket < 2 * b
    # explicit floors (jit-reuse tuning) still respected
    assert bucket_blocks(3, min_bucket=256) == 256
    assert bucket_blocks(300, min_bucket=64) == 512


@pytest.mark.slow
def test_batched_auto_routes_oversized_mix_to_hbm():
    """One n_cols=20k graph in an otherwise-small batch: auto must pick the
    HBM-gather kernel and still match the per-graph blocked oracle."""
    from repro.kernels.ops import spmm_blocked

    cfg = PartitionConfig()
    graphs = [make_wide_csr(500, 20_000, 1_500, seed=1),
              gcn_normalize(make_powerlaw_csr(n=90, seed=2)),
              gcn_normalize(make_powerlaw_csr(n=130, seed=3))]
    plans = [build_partition_plan(g, cfg) for g in graphs]
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.normal(size=(g.n_cols, 8)), jnp.float32)
          for g in graphs]

    outs, decision = spmm_batched(
        [p.slabs for p in plans], xs, [p.n_rows for p in plans],
        backend="auto", return_decision=True)
    assert decision.backend == "hbm"
    assert decision.n_rows == sum(g.n_cols for g in graphs)
    for p, x, out in zip(plans, xs, outs):
        ref = spmm_blocked(p.slabs["colidx"], p.slabs["values"],
                           p.slabs["rowloc"], p.slabs["out_row"],
                           x, p.n_rows)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_batched_forced_resident_raises_on_oversized_mix():
    from repro.kernels.router import VmemBudgetError

    cfg = PartitionConfig()
    graphs = [make_wide_csr(500, 20_000, 1_500, seed=1),
              gcn_normalize(make_powerlaw_csr(n=90, seed=2))]
    plans = [build_partition_plan(g, cfg) for g in graphs]
    xs = [jnp.zeros((g.n_cols, 8), jnp.float32) for g in graphs]
    with pytest.raises(VmemBudgetError, match="VMEM budget"):
        spmm_batched([p.slabs for p in plans], xs,
                     [p.n_rows for p in plans], backend="pallas")


@pytest.mark.slow
def test_batched_auto_windowed_middle_regime():
    """A batch of individually-resident graphs whose concatenation lands in
    the windowed regime (4096 < N_pad <= 16384)."""
    cfg = PartitionConfig()
    graphs = [make_wide_csr(400, 2_500, 1_200, seed=10 + i)
              for i in range(3)]
    plans = [build_partition_plan(g, cfg) for g in graphs]
    rng = np.random.default_rng(4)
    xs = [jnp.asarray(rng.normal(size=(g.n_cols, 16)), jnp.float32)
          for g in graphs]

    outs, decision = spmm_batched(
        [p.slabs for p in plans], xs, [p.n_rows for p in plans],
        backend="auto", return_decision=True)
    assert decision.backend == "windowed" and decision.num_windows == 2
    _check_parity(plans, xs, "blocked")   # blocked twin agrees per graph
    for p, x, out in zip(plans, xs, outs):
        ref = spmm_block_slabs(p.slabs["colidx"], p.slabs["values"],
                               p.slabs["rowloc"], p.slabs["out_row"],
                               x, p.n_rows)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_batch_graph_slabs_sentinel_remap():
    """Per-graph drop sentinels must map to the single batch sentinel, never
    to another graph's live rows."""
    cfg = PartitionConfig()
    gs = [gcn_normalize(make_powerlaw_csr(n=60 + i * 20, seed=40 + i))
          for i in range(3)]
    plans = [build_partition_plan(g, cfg) for g in gs]
    merged, out_off, col_off, n_out = batch_graph_slabs(
        [p.slabs for p in plans], [p.n_rows for p in plans],
        [p.n_cols for p in plans])
    assert n_out == sum(p.n_rows for p in plans)
    orw = merged["out_row"]
    assert orw.max() == n_out, "batch sentinel present"
    # every non-sentinel out_row of graph i lies inside graph i's row span
    b0 = 0
    for i, p in enumerate(plans):
        span = orw[b0:b0 + p.num_blocks]
        live = span[span != n_out]
        assert live.min() >= out_off[i] and live.max() < out_off[i + 1]
        b0 += p.num_blocks

