"""The system under test, built from a configuration file.

A configuration (``configs/<name>.json``) names its graph kind, its model
kind and widths, and the engine's settings. This module builds the graphs
by the graph kind's file, puts them through the model's ``prepare``,
registers them with the program's engine, and makes the traffic kind's loop.
It is the only place the benchmark touches the program, apart from the
metric readers' counters.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

from bench import loader
from bench.data import CSR


def jax_seed(seed: int, stream: int) -> int:
    """A 32-bit key seed derived from any non-negative ``--seed``."""
    return int(np.random.SeedSequence([int(seed) % 2**63, stream])
               .generate_state(1)[0])


def build_graphs(graph_cfg: Dict, seed: int, prepare: Callable[[CSR], CSR]
                 ) -> List[CSR]:
    """The configuration's graphs from the seed, by its graph kind's file,
    each through the model's ``prepare``."""
    kind = loader.load("data", graph_cfg["kind"])
    return [prepare(g) for g in kind.build(graph_cfg, seed)]


def make_engine(engine_cfg: Dict):
    """The program's serving engine with the configuration's settings."""
    from repro.serve.graph_engine import GraphServeEngine
    return GraphServeEngine(**engine_cfg)


def register(engine, graph_id: str, g: CSR) -> None:
    from repro.core.graph import CSRGraph
    rowptr, colidx, values = g
    engine.register_graph(graph_id, CSRGraph(rowptr, colidx, values,
                                             len(rowptr) - 1))


def make_loop(config: Dict, traffic: Dict, seed: int, devices: Sequence):
    """The loop of the traffic's kind, driving the configuration's model."""
    model = loader.load("models", config["model"]["kind"])
    return loader.load("loops", traffic["kind"]).Loop(
        config, traffic, seed, devices, model)
