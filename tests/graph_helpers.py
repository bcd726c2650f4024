"""Small CSR graphs the tests build: a power-law graph and a sparse wide
one."""
import numpy as np


def make_powerlaw_csr(n=200, seed=0, zipf=1.8, cap=500, n_cols=None):
    """Shared helper: small power-law CSR graph."""
    from repro.core.graph import csr_from_edges
    rng = np.random.default_rng(seed)
    deg = np.minimum(rng.zipf(zipf, n), cap)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n_cols or n, len(src))
    return csr_from_edges(src, dst, n_cols or n)


def make_wide_csr(n_rows, n_cols, nnz, seed):
    """Sparse rectangular graph: few rows, a huge feature-row space — the
    shape that overflows the resident VMEM budget while staying CI-cheap."""
    from repro.core.graph import csr_from_edges, gcn_normalize
    rng = np.random.default_rng(seed)
    src = np.sort(rng.integers(0, n_rows, nnz))
    dst = rng.integers(0, n_cols, nnz)
    return gcn_normalize(csr_from_edges(src, dst, n_cols))
