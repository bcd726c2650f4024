"""CSR container + O(n) preprocessing correctness."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.graph import (
    CSRGraph, counting_sort_by_degree, csr_from_edges, csr_transpose,
    degree_sort_csr, degrees_from_rowptr, gcn_normalize,
)
from graph_helpers import make_powerlaw_csr


@settings(max_examples=30, deadline=None)
@given(degs=st.lists(st.integers(0, 50), min_size=1, max_size=200))
def test_counting_sort_stable_ascending(degs):
    d = np.array(degs)
    perm = counting_sort_by_degree(d)
    s = d[perm]
    assert np.all(np.diff(s) >= 0)
    # stability: equal degrees keep original relative order
    for val in np.unique(d):
        orig = np.flatnonzero(d == val)
        assert np.array_equal(perm[s == val], orig)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 300), seed=st.integers(0, 999))
def test_degree_sort_preserves_matrix(n, seed):
    g = make_powerlaw_csr(n=n, seed=seed)
    gs = degree_sort_csr(g)
    gs.validate()
    # row contents preserved under permutation
    dense = g.to_dense()
    dense_s = gs.to_dense()
    assert np.allclose(dense_s, dense[gs.perm])
    # degrees ascending
    assert np.all(np.diff(degrees_from_rowptr(gs.rowptr)) >= 0)


def test_gcn_normalize_symmetric():
    g = make_powerlaw_csr(n=50, seed=1)
    gn = gcn_normalize(g)
    a = gn.to_dense()
    deg = np.asarray((make_powerlaw_csr(n=50, seed=1).to_dense()
                      + np.eye(50) > 0))  # structure only
    # row sums of D^-1/2 (A+I) D^-1/2 bounded by sqrt(deg) ratios; spot check
    # the self-loop value: 1/deg for isolated-ish nodes
    gi = gcn_normalize(CSRGraph(np.arange(6), np.zeros(5, np.int64),
                                np.ones(5, np.float32), 5))
    d = gi.to_dense()
    # each row had 1 edge to node 0 + self loop
    assert d.shape == (5, 5)
    assert np.isfinite(d).all()


def test_csr_from_edges_roundtrip():
    src = np.array([2, 0, 1, 0, 2])
    dst = np.array([1, 2, 0, 1, 2])
    g = csr_from_edges(src, dst, 3)
    g.validate()
    d = g.to_dense()
    expect = np.zeros((3, 3))
    for s, t in zip(src, dst):
        expect[s, t] += 1
    assert np.allclose(d, expect)


# --------------------------------------------------------------- transpose
def test_csr_transpose_dense_parity():
    g = make_powerlaw_csr(n=60, seed=3)
    t = csr_transpose(g)
    t.validate()
    assert np.array_equal(t.to_dense(), g.to_dense().T)


def test_csr_transpose_rectangular_and_values():
    dst = np.array([1, 4, 0, 4])
    vals = np.array([1.5, -2.0, 0.25, 7.0], dtype=np.float32)
    g = CSRGraph(np.array([0, 2, 2, 3, 4]), dst.astype(np.int64),
                 vals, n_cols=5)
    t = csr_transpose(g)
    assert t.n_rows == 5 and t.n_cols == 4
    assert np.array_equal(t.to_dense(), g.to_dense().T)


def test_csr_transpose_within_row_source_order():
    # transposed rows list sources ASCENDING (row-major scan is stable)
    g = make_powerlaw_csr(n=80, seed=9)
    t = csr_transpose(g)
    for r in range(t.n_rows):
        lo, hi = t.rowptr[r], t.rowptr[r + 1]
        assert np.all(np.diff(t.colidx[lo:hi]) >= 0)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 200), seed=st.integers(0, 999))
def test_csr_transpose_involution(n, seed):
    g = make_powerlaw_csr(n=n, seed=seed)
    tt = csr_transpose(csr_transpose(g))
    assert np.array_equal(tt.to_dense(), g.to_dense())
