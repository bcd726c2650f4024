"""The benchmark's own graph data: a bounded power-law generator and GCN
normalisation.

The normalisation is a copy of the repository's, kept here so that a later
change to the program's data code cannot move the yardstick. Everything is
host numpy, deterministic in its arguments, and returns plain
``(rowptr, colidx, values)`` arrays.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

CSR = Tuple[np.ndarray, np.ndarray, np.ndarray]   # rowptr, colidx, values


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


def csr_from_edges(src: np.ndarray, dst: np.ndarray, n: int) -> CSR:
    """CSR of an edge list, rows in source order, no deduplication."""
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    rowptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=rowptr[1:])
    return rowptr, dst.astype(np.int64), np.ones(len(src), np.float32)


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


def gcn_normalize(g: CSR) -> CSR:
    """A' = D^-1/2 (A + I) D^-1/2 with D the row degrees of A + I: a self
    loop closes every row, values computed in float64, stored float32."""
    rowptr, colidx, values = g
    n = len(rowptr) - 1
    deg = np.diff(rowptr)
    new_rowptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg + 1, out=new_rowptr[1:])
    keep = np.ones(int(new_rowptr[-1]), dtype=bool)
    keep[new_rowptr[1:] - 1] = False              # last slot of each row
    cols = np.empty(int(new_rowptr[-1]), dtype=np.int64)
    vals = np.empty(int(new_rowptr[-1]), dtype=np.float64)
    cols[keep], vals[keep] = colidx, values
    cols[~keep], vals[~keep] = np.arange(n), 1.0
    dinv = 1.0 / np.sqrt(np.diff(new_rowptr).astype(np.float64))
    row_of = np.repeat(np.arange(n), np.diff(new_rowptr))
    vals = vals * dinv[row_of] * dinv[cols]
    return new_rowptr, cols, vals.astype(np.float32)
