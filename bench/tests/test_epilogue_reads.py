"""``epilogue_reads.full`` on a tiny forward loop through the ``hbm``
kernel: each dispatch's epilogue reads its n_out answer rows and one row
per block, so the reader gives 1 + B / n_rows; the parent's counters,
which lack ``epilogue_rows``, give nothing."""
import jax
import pytest

from bench import harness, loader, system
from bench.tests.tiny import tiny_config

PARENT_STATS = {"rows_served": 300, "batches_dispatched": 3}


def read(run):
    return loader.load("metrics", "epilogue_reads.full").read(run)


def test_epilogue_reads_one_row_per_answer_row_and_block():
    config = tiny_config("gcn3-arxiv")
    config["engine"] = {"backend": "hbm"}
    loop = system.make_loop(config,
                            loader.load_json("traffic", "fullgraph-loop"),
                            2**31 + 93, jax.devices()[:1])
    try:
        loop.setup()
        stats0 = loop.engine.stats()
        loop.window(0.2)
        stats1 = loop.engine.stats()
    finally:
        loop.close()
    run = harness.Run(stats0=stats0, stats1=stats1, trace=None)
    # one request a dispatch: its rows are the graph's, its blocks bucketed
    n = run.counter("batches_dispatched")
    rows, blocks = run.counter("rows_served") / n, run.counter(
        "padded_blocks") / n
    assert n == run.counter("routed_hbm") > 0
    assert read(run) == pytest.approx(1 + blocks / rows)


def test_epilogue_reads_gives_nothing_without_its_counter():
    run = harness.Run(stats0=dict(PARENT_STATS),
                      stats1=dict(PARENT_STATS, rows_served=600), trace=None)
    assert read(run) is None
