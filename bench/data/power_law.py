"""Graph kind ``power_law``: one simple directed graph with a bounded
power-law out-degree sequence, from the seed's stream 0."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.data import CSR, csr_from_edges, rng_for


def build(graph_cfg: Dict, seed: int) -> List[CSR]:
    return [power_law_graph(graph_cfg["nodes"], graph_cfg["edges"],
                            graph_cfg["max_degree"], rng_for(seed, 0))]


def power_law_degrees(n: int, m_edges: int, max_degree: int) -> np.ndarray:
    """``n`` degrees from 1 to ``max_degree`` that sum to ``m_edges``: the
    evenly spaced quantiles of a continuous power law truncated to
    ``[1, max_degree]``, its exponent found by bisection so that the mean is
    ``m_edges / n``, rounded by largest remainders. No seed enters."""
    u = (np.arange(n) + 0.5) / n

    def quantiles(a: float) -> np.ndarray:
        return (1.0 - u * (1.0 - max_degree ** (1.0 - a))) ** (1.0 / (1.0 - a))

    lo, hi = 1.0 + 1e-6, 8.0
    for _ in range(100):
        a = 0.5 * (lo + hi)
        lo, hi = (a, hi) if quantiles(a).mean() > m_edges / n else (lo, a)
    x = quantiles(0.5 * (lo + hi))
    x *= m_edges / x.sum()
    deg = np.floor(x).astype(np.int64)
    deg[np.argsort(deg - x, kind="stable")[:m_edges - int(deg.sum())]] += 1
    return deg


def power_law_graph(n: int, m_edges: int, max_degree: int,
                    rng: np.random.Generator) -> CSR:
    """A simple directed graph with ``m_edges`` edges: out-degrees from
    ``power_law_degrees`` dealt to the nodes in a seeded order, endpoints
    skewed quadratically by a seeded popularity rank. No edge repeats and
    none is a self loop: a drawn endpoint that would be one is drawn again.
    Every seed gives the same degrees, so the same work, on another graph."""
    if not 0 < max_degree < n:
        raise ValueError(f"max_degree must lie in [1, {n - 1}]")
    deg = rng.permutation(power_law_degrees(n, m_edges, max_degree))
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    by_rank = rng.permutation(n)
    dst = np.empty_like(src)
    todo = np.arange(len(src))
    while len(todo):
        u = rng.random(len(todo))
        dst[todo] = by_rank[np.minimum((n * u ** 2).astype(np.int64), n - 1)]
        _, first = np.unique(src * n + dst, return_index=True)
        again = np.ones(len(src), dtype=bool)
        again[first] = False
        todo = np.flatnonzero(again | (dst == src))
    return csr_from_edges(src, dst, n)
