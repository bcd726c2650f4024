"""The benchmark's own graph data. Each graph kind is a file here,
``<kind>.py``, found by the name a configuration's ``graph.kind`` gives,
with ``build(graph_cfg, seed) -> list[CSR]``; this module holds what the
kinds share. Everything is host numpy, deterministic in its arguments, and
returns plain ``(rowptr, colidx, values)`` arrays.
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
