"""``gather_passes.full`` on a tiny forward loop through the ``hbm`` kernel:
at the cell's aggregation widths (256 and 40, F_pad at most 2048) every
block gathers its rows once, so the reader gives 1.0; the parent's
counters, which lack ``hbm_gather_passes``, give nothing."""
import jax

from bench import harness, loader, system
from bench.tests.tiny import tiny_config

PARENT_STATS = {"routed_hbm": 3, "batches_dispatched": 3}


def read(run):
    return loader.load("metrics", "gather_passes.full").read(run)


def test_gather_passes_reads_one_on_a_tiny_hbm_run():
    config = tiny_config("gcn3-arxiv")
    config["model"] = dict(config["model"], widths=[16, 256, 40])
    config["engine"] = {"backend": "hbm"}
    loop = system.make_loop(config,
                            loader.load_json("traffic", "fullgraph-loop"),
                            2**31 + 91, jax.devices()[:1])
    try:
        loop.setup()
        stats0 = loop.engine.stats()
        loop.window(0.2)
        stats1 = loop.engine.stats()
    finally:
        loop.close()
    run = harness.Run(stats0=stats0, stats1=stats1, trace=None)
    assert run.counter("routed_hbm") == 2 * loop.passes
    assert read(run) == 1.0


def test_gather_passes_gives_nothing_without_its_counter():
    run = harness.Run(stats0=dict(PARENT_STATS),
                      stats1=dict(PARENT_STATS, routed_hbm=6), trace=None)
    assert read(run) is None


def test_gather_passes_gives_nothing_without_an_hbm_dispatch():
    stats = {"routed_hbm": 3, "hbm_gather_passes": 5}
    assert read(harness.Run(stats0=stats, stats1=dict(stats),
                            trace=None)) is None
