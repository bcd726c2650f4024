"""The GAT cell (``gat3-arxiv`` at its ``tiny()`` size on the CPU): faults
planted in the per-call attention values come out as not correct, the
attention work count agrees with a hand count, and the cell's new metric
readers read nothing from a run of a program without attention."""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.spmm_batched as spmm_batched
from bench import harness, loader
from bench.data import csr_from_edges
from bench.tests.tiny import run_tiny, tiny_config
from bench.trace import HOST_PLANE, MODULES_LINE, OPS_LINE, Event, TraceSummary
from repro.kernels.edge_softmax import ATTENTION

WORKLOAD = "arxiv-gat-fullgraph"
SEED = 2**31 + 77
MODEL = loader.load("models", "graph_attention")
real_values = spmm_batched.edge_softmax_slabs


def softmax_counts_padding(colidx, values, rowloc, out_row, nnz, *rest):
    """Every slot of a block counted as live, its padding slots in the
    softmax of the block's last live row (the row they trail)."""
    C = colidx.shape[1]
    last = jnp.take_along_axis(rowloc, jnp.maximum(nnz - 1, 0)[:, None], 1)
    live = jnp.arange(C)[None, :] < nnz[:, None]
    return real_values(colidx, values, jnp.where(live, rowloc, last),
                       out_row, jnp.full_like(nnz, C), *rest)


def split_row_normalised_per_block(colidx, values, rowloc, out_row, nnz,
                                   kind, *rest):
    """A row split over several blocks normalised within each block, so its
    weights sum to the number of its blocks."""
    vals = real_values(colidx, values, rowloc, out_row, nnz, kind, *rest)
    first = out_row[:, 0]
    split = (((first == jnp.roll(first, 1)) | (first == jnp.roll(first, -1)))
             & (nnz > 0))
    fix = split[:, None, None] & (kind == ATTENTION)[:, :, None]
    return jnp.where(fix, vals / vals.sum(-1, keepdims=True), vals)


def heads_swapped(*args):
    """The values of heads 0 and 1 exchanged."""
    vals = real_values(*args)
    order = [1, 0] + list(range(2, vals.shape[1]))
    return vals[:, order]


@pytest.mark.parametrize("fault", [softmax_counts_padding,
                                   split_row_normalised_per_block,
                                   heads_swapped])
def test_attention_fault_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(spmm_batched, "edge_softmax_slabs", fault)
    result = run_tiny(WORKLOAD, seed=SEED)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def test_tiny_graph_has_split_rows_and_padding():
    """The faults above have something to break at the tiny size: a row
    longer than a block's 256 slots, and rows short enough to leave slots
    empty."""
    from bench import system
    config = tiny_config("gat3-arxiv")
    (g,) = system.build_graphs(config["graph"], SEED, MODEL.prepare)
    deg = np.diff(g[0])
    assert deg.max() > 256 and deg.min() <= 3


def test_prepare_is_bidirected_with_self_loops():
    g = MODEL.prepare(csr_from_edges(np.array([0, 0, 1, 2]),
                                     np.array([1, 2, 0, 2]), 3))
    rowptr, colidx, values = g
    rows = np.repeat(np.arange(3), np.diff(rowptr))
    assert sorted(zip(rows.tolist(), colidx.tolist())) == [
        (0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (2, 0), (2, 2)]
    assert np.all(values == 1.0)


def test_attention_work_by_hand():
    """A 5-node ring with one chord, 0-1-2-3-4-0 and 0-2, made bidirected
    with self loops: 6 * 2 + 5 = 17 nonzeros; 2 heads of 3."""
    rowptr, colidx, _ = MODEL.prepare(csr_from_edges(
        np.array([0, 1, 2, 3, 4, 0]), np.array([1, 2, 3, 4, 0, 2]), 5))
    nnz, n = len(colidx), len(rowptr) - 1
    assert (nnz, n) == (17, 5)
    w = MODEL.attention_work(nnz, n, 2, 3)
    assert w.flops == 17 * 2 * (2 * 3 + 4)                  # 340
    # CSR 17 * 4 + 6 * 4; el, er 2 * 5 * 2 * 4; z, out 2 * 5 * 2 * 3 * 4
    assert w.bytes == 68 + 24 + 80 + 240


def _run(stats=None, events=None, work=None):
    trace = TraceSummary(events) if events else None
    stats = stats or {}
    return harness.Run(stats0=stats, stats1=stats, trace=trace, units=3,
                       peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                       work=work or {})


def _gcn_trace():
    ms = 1e6
    return [Event(HOST_PLANE, "python", "bench.window", 0, 100 * ms),
            Event("/device:TPU:0", OPS_LINE, "fusion", 10 * ms, 20 * ms),
            Event("/device:TPU:0", MODULES_LINE,
                  "jit__spmm_block_slabs_hbm(2)", 10 * ms, 20 * ms)]


@pytest.mark.parametrize("metric", ["attn_roofline.full",
                                    "edge_softmax_ms.full",
                                    "scores_ms.full"])
def test_new_metrics_read_nothing_without_attention(metric):
    """A program without attention (the parent's, or the GCN cell's) has no
    scores counters and no edge-softmax modules: each reader gives None
    and raises nothing."""
    reader = loader.load("metrics", metric)
    stats = {"batches_dispatched": 3.0, "dispatch_merge_s": 0.1}
    work = {"spmm": MODEL.attention_work(10, 5, 1, 1)}
    assert reader.read(_run(stats, _gcn_trace(), work)) is None


def test_new_metrics_read_an_attention_run():
    ms = 1e6
    events = _gcn_trace() + [
        Event("/device:TPU:0", MODULES_LINE, "jit__edge_softmax_slabs(7)",
              40 * ms, 6 * ms),
        Event("/device:TPU:0", OPS_LINE, "fusion.2", 40 * ms, 6 * ms)]
    stats0 = {"dispatch_scores_s": 1.0, "attn_dispatches": 10}
    stats1 = {"dispatch_scores_s": 1.006, "attn_dispatches": 13}
    work = {"attention": MODEL.attention_work(10**6, 10**5, 2, 8)}
    run = _run(events=events, work=work)
    run.stats0, run.stats1 = stats0, stats1
    assert loader.load("metrics", "edge_softmax_ms.full").read(run) == \
        pytest.approx(2.0)
    assert loader.load("metrics", "scores_ms.full").read(run) == \
        pytest.approx(2.0)
    roofline = loader.load("metrics", "attn_roofline.full").read(run)
    want = 100.0 * work["attention"].bytes / 1e9 / 0.026
    assert roofline == pytest.approx(want)
