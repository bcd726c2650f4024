"""The system under test, built from a configuration file.

A configuration (``configs/<name>.json``) names its graph data, its model
widths and the engine's settings. This module turns it into host CSR arrays
(``bench.data``), registers them with the program's engine, and makes the
dense inputs from the seed. It is the only place the benchmark touches the
program, apart from the metric readers' counters.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench.data import graphs as data

BENCH_DIR = Path(__file__).resolve().parent


def load_json(kind: str, name: str) -> Dict:
    return json.loads((BENCH_DIR / kind / f"{name}.json").read_text())


def jax_seed(seed: int, stream: int) -> int:
    """A 32-bit key seed derived from any non-negative ``--seed``."""
    return int(np.random.SeedSequence([int(seed) % 2**63, stream])
               .generate_state(1)[0])


def build_graphs(graph_cfg: Dict, seed: int) -> List[data.CSR]:
    """The configuration's graphs, GCN-normalised, from the seed."""
    kind = graph_cfg["kind"]
    if kind == "power_law":
        g = data.power_law_graph(graph_cfg["nodes"], graph_cfg["edges"],
                                 graph_cfg["max_degree"],
                                 data.rng_for(seed, 0))
        return [data.gcn_normalize(g)]
    raise ValueError(f"unknown graph kind {kind!r}")


def make_engine(engine_cfg: Dict):
    """The program's serving engine with the configuration's settings."""
    from repro.serve.graph_engine import GraphServeEngine
    return GraphServeEngine(**engine_cfg)


def register(engine, graph_id: str, g: data.CSR) -> None:
    from repro.core.graph import CSRGraph
    rowptr, colidx, values = g
    engine.register_graph(graph_id, CSRGraph(rowptr, colidx, values,
                                             len(rowptr) - 1))
