"""Grouped-GEMM Pallas kernel vs oracle: shape/dtype/group sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.ops import grouped_matmul_blocked
from repro.kernels.ref import grouped_matmul_ref


def _case(E, K, N, mt, sizes, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    gsz = np.asarray(sizes, np.int32)
    M = int(gsz.sum())
    x = rng.normal(size=(M, K)).astype(dtype) * 0.2
    w = rng.normal(size=(E, K, N)).astype(dtype) * 0.2
    be = np.repeat(np.arange(E), gsz // mt).astype(np.int32)
    out = grouped_matmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(be),
                         m_tile=mt)
    ref = grouped_matmul_ref(jnp.asarray(x), jnp.asarray(w), jnp.asarray(gsz))
    return np.asarray(out), np.asarray(ref)


@pytest.mark.parametrize("K,N,mt", [(64, 64, 32), (256, 128, 128), (128, 96, 16),
                                    (512, 256, 64)])
def test_shapes(K, N, mt):
    out, ref = _case(4, K, N, mt, [mt * 2, 0, mt, mt * 3])
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)


def test_empty_and_single_groups():
    out, ref = _case(5, 64, 64, 16, [0, 16, 0, 0, 48])
    np.testing.assert_allclose(out, ref, atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(e=st.integers(1, 6), nblocks=st.lists(st.integers(0, 4), min_size=1,
                                             max_size=6), seed=st.integers(0, 99))
def test_hypothesis_groups(e, nblocks, seed):
    nblocks = (nblocks + [1] * e)[:e]
    if sum(nblocks) == 0:
        nblocks[0] = 1
    mt = 16
    out, ref = _case(e, 32, 32, mt, [b * mt for b in nblocks], seed=seed)
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=1e-3)


def test_blocked_twin_matches_kernel():
    rng = np.random.default_rng(4)
    E, K, N, mt = 3, 64, 48, 8
    gsz = np.array([16, 8, 24], np.int32)
    M = int(gsz.sum())
    x = rng.normal(size=(M, K)).astype(np.float32)
    w = rng.normal(size=(E, K, N)).astype(np.float32)
    be = np.repeat(np.arange(E), gsz // mt).astype(np.int32)
    a = grouped_matmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(be), m_tile=mt)
    b = grouped_matmul_blocked(jnp.asarray(x), jnp.asarray(w), jnp.asarray(be),
                               m_tile=mt)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
