"""Pallas SpMM kernel: shape/dtype sweep + hypothesis graphs vs ref oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.graph import degree_sort_csr, gcn_normalize
from repro.core.partition import (block_level_partition, get_partition_patterns,
                                  pack_slabs)
from repro.kernels.ref import csr_spmm_ref
from repro.kernels.spmm_accel import spmm_block_slabs
from conftest import make_powerlaw_csr


def _run(g, X, mode="tpu", mbw=32, mwn=8, kernel=None):
    gs = degree_sort_csr(g)
    pats = get_partition_patterns(mbw, mwn, mode=mode)
    bp = block_level_partition(gs, pats)
    slabs = pack_slabs(gs, bp)
    kern = kernel or spmm_block_slabs
    out_sorted = kern(
        jnp.asarray(slabs["colidx"]), jnp.asarray(slabs["values"]),
        jnp.asarray(slabs["rowloc"]), jnp.asarray(slabs["out_row"]),
        jnp.asarray(X), gs.n_rows)
    out = np.empty_like(np.asarray(out_sorted))
    out[gs.perm] = np.asarray(out_sorted)
    return out


@pytest.mark.parametrize("F", [1, 16, 32, 96, 128, 200, 256])
def test_feature_dims_sweep(F):
    """Paper Fig. 6 regime: column dims 16..128 (+ ragged edges)."""
    g = gcn_normalize(make_powerlaw_csr(n=150, seed=0))
    X = np.random.default_rng(0).normal(size=(150, F)).astype(np.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, jnp.asarray(X)))
    out = _run(g, X)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-4), (jnp.bfloat16, 5e-2)])
def test_dtypes(dtype, atol):
    g = gcn_normalize(make_powerlaw_csr(n=100, seed=2))
    X = (np.random.default_rng(1).normal(size=(100, 64)) * 0.5)
    Xj = jnp.asarray(X.astype(np.float32)).astype(dtype)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values,
                                  Xj.astype(jnp.float32)))
    out = _run(g, np.asarray(Xj.astype(jnp.float32)))
    np.testing.assert_allclose(out, ref, atol=atol, rtol=1e-2)


@pytest.mark.parametrize("mode,mbw,mwn", [("paper", 12, 32), ("paper", 4, 8),
                                          ("tpu", 64, 4), ("tpu", 16, 16)])
def test_partition_configs(mode, mbw, mwn):
    g = gcn_normalize(make_powerlaw_csr(n=220, seed=3, zipf=1.4))
    X = np.random.default_rng(2).normal(size=(220, 48)).astype(np.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, jnp.asarray(X)))
    out = _run(g, X, mode=mode, mbw=mbw, mwn=mwn)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=1e-4)


@settings(max_examples=12, deadline=None)
@given(n=st.integers(5, 250), seed=st.integers(0, 10_000),
       zipf=st.sampled_from([1.3, 1.8, 2.5]), F=st.integers(1, 80))
def test_hypothesis_random_graphs(n, seed, zipf, F):
    g = gcn_normalize(make_powerlaw_csr(n=n, seed=seed, zipf=zipf))
    X = np.random.default_rng(seed).normal(size=(n, F)).astype(np.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, jnp.asarray(X)))
    out = _run(g, X)
    np.testing.assert_allclose(out, ref, atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("F", [32, 96, 128, 200, 256, 300, 2100])
def test_hbm_gather_variant(F):
    """HBM-resident X kernel (pipelined one-row DMA gather) vs oracle: one
    full-width gather per block up to F_pad 384 here (W = F_pad), and the
    tiled fallback at F=2100 (F_pad 2176, W = 128: seventeen planes)."""
    from repro.kernels.spmm_hbm import spmm_block_slabs_hbm
    g = gcn_normalize(make_powerlaw_csr(n=140, seed=4))
    X = np.random.default_rng(0).normal(size=(140, F)).astype(np.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, jnp.asarray(X)))
    out = _run(g, X, kernel=spmm_block_slabs_hbm)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("F,width", [(40, 128), (256, 256), (2048, 2048),
                                     (2176, 128)])
def test_hbm_gather_width(F, width):
    """The widest multiple of 128 dividing F_pad whose [C, W] f32 scratch
    fits the 2 MiB budget (C=256: W <= 2048); 2176 = 17 x 128 tiles."""
    from repro.kernels.router import hbm_gather_width, pad_features
    assert hbm_gather_width(pad_features(F, 128), 256, 4) == width


def test_hbm_matches_resident_kernel():
    from repro.kernels.spmm_hbm import spmm_block_slabs_hbm
    g = gcn_normalize(make_powerlaw_csr(n=120, seed=5, zipf=1.4))
    X = np.random.default_rng(1).normal(size=(120, 64)).astype(np.float32)
    a = _run(g, X)
    b = _run(g, X, kernel=spmm_block_slabs_hbm)
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_empty_rows_and_rectangular():
    # rows with zero degree + rectangular (n_rows != n_cols)
    from repro.core.graph import csr_from_edges
    src = np.array([0, 0, 3, 3, 3, 3])
    dst = np.array([1, 4, 0, 1, 2, 4])
    g = csr_from_edges(src, dst, 4)
    g = type(g)(g.rowptr, g.colidx, g.values, 5)  # 4 x 5, rows 1,2 empty
    X = np.random.default_rng(3).normal(size=(5, 40)).astype(np.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, jnp.asarray(X)))
    out = _run(g, X)
    np.testing.assert_allclose(out, ref, atol=2e-4)
    assert np.all(out[1] == 0) and np.all(out[2] == 0)
