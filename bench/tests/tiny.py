"""A small stand-in for the benchmark's configuration, so that a whole run
fits a CPU test: the same file with fewer nodes and narrower features."""
import time

import jax

from bench import system
from bench.harness import run_cell


def tiny_config():
    cfg = system.load_json("configs", "gcn3-arxiv")
    cfg["graph"] = dict(cfg["graph"], nodes=3000, edges=20000, max_degree=300)
    cfg["model"] = {"widths": [16, 32, 8]}
    return cfg


def run_tiny(workload: str, seed: int, seconds: float = 0.5):
    return run_cell(workload, seed, seconds, False,
                    t_start=time.perf_counter(), devices=jax.devices()[:1],
                    config=tiny_config())
