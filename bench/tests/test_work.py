"""Compulsory work counts against hand sums, and the peaks table."""
import numpy as np
import pytest

from bench.data import graphs as data
from bench.work import compulsory_s, dense_work, load_peaks, spmm_work


def tiny_graph():
    # 3 nodes, edges 0->1, 1->2, 2->0, 2->1; + self loops after normalisation
    return data.gcn_normalize(data.csr_from_edges(
        np.array([0, 1, 2, 2]), np.array([1, 2, 0, 1]), 3))


def test_spmm_work_by_hand():
    rowptr, colidx, _ = tiny_graph()
    nnz, n, f = len(colidx), len(rowptr) - 1, 5
    assert nnz == 7
    w = spmm_work(nnz, n, n, f)
    # A' in CSR: 7 * (4 + 4) + 4 * 4; X: 3 * 5 * 4; Y: 3 * 5 * 4
    assert w.bytes == 7 * 8 + 16 + 60 + 60
    assert w.flops == 2 * 7 * 5


def test_dense_work_by_hand():
    w = dense_work(10, 4, 3)
    assert w.flops == 2 * 10 * 4 * 3
    assert w.bytes == (40 + 12 + 30) * 4


def test_forward_work_of_arxiv_pass():
    """One 128-256-256-40 pass over the Arxiv counts is about 1.59 GB and
    1.94 ms of compulsory time on a v5e."""
    n, nnz = 169_343, 1_166_243 + 169_343
    total = spmm_work(nnz, n, n, 256).scaled(2) + spmm_work(nnz, n, n, 40)
    for fi, fo in ((128, 256), (256, 256), (256, 40)):
        total = total + dense_work(n, fi, fo)
    peaks = load_peaks("TPU v5 lite")
    per_layer = sum(compulsory_s(w, peaks) for w in (
        dense_work(n, 128, 256), spmm_work(nnz, n, n, 256),
        dense_work(n, 256, 256), spmm_work(nnz, n, n, 256),
        dense_work(n, 256, 40), spmm_work(nnz, n, n, 40)))
    assert 1.55e9 < total.bytes < 1.65e9
    assert 1.85e-3 < per_layer < 2.05e-3


def test_compulsory_time_takes_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert compulsory_s(spmm_work(1, 1, 1, 1), peaks) == pytest.approx(
        (8 + 8 + 4 + 4) / 10.0)
    assert compulsory_s(dense_work(1000, 1000, 1000), peaks) == \
        pytest.approx(2e9 / 100.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        load_peaks("TPU v99")
