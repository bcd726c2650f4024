"""Small stand-ins for the benchmark's configurations, so that a whole run
fits a CPU test: each cell's configuration at the size its model file's
``tiny()`` gives."""
import time
from typing import Dict

import jax

from bench import loader
from bench.harness import cell_of, load_spec, run_cell


def tiny_config(name: str) -> Dict:
    config = loader.load_json("configs", name)
    return loader.load("models", config["model"]["kind"]).tiny(config)


def tiny_cell(workload: str):
    """The cell's tiny configuration and its traffic mix."""
    cell = cell_of(load_spec(), workload)
    return (tiny_config(cell["config"]),
            loader.load_json("traffic", cell["traffic"]))


def run_tiny(workload: str, seed: int, seconds: float = 0.5):
    return run_cell(workload, seed, seconds, False,
                    t_start=time.perf_counter(), devices=jax.devices()[:1],
                    config=tiny_cell(workload)[0])
