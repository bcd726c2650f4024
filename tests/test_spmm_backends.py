"""All SpMM backends agree; 18-benchmark-graph analogues (reduced sizes)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import gcn_normalize
from repro.core.spmm import make_accel_spmm
from repro.data.graphs import BENCHMARK_GRAPHS, make_power_law_graph
from repro.kernels.ref import csr_spmm_ref
from graph_helpers import make_powerlaw_csr


def test_all_backends_agree():
    g = gcn_normalize(make_powerlaw_csr(n=300, seed=5))
    X = jnp.asarray(np.random.default_rng(0).normal(size=(300, 64)),
                    dtype=jnp.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, X))
    op = make_accel_spmm(g, with_baselines=True)
    for be in ["pallas", "blocked", "segment", "warp", "dense"]:
        out = np.asarray(op(X, backend=be))
        np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3,
                                   err_msg=f"backend {be}")


@pytest.mark.parametrize("name", sorted(BENCHMARK_GRAPHS))
def test_benchmark_graph_analogues(name):
    """Every Table-I graph analogue (scaled to ~1/500 size for CI speed):
    correctness of the full preprocessing + blocked backend."""
    n_full, e_full, scale = BENCHMARK_GRAPHS[name]
    n = max(50, n_full // 500)
    e = max(100, int(e_full * scale) // 500)
    g = gcn_normalize(make_power_law_graph(n, e, seed=hash(name) % 2**31))
    X = jnp.asarray(np.random.default_rng(1).normal(size=(g.n_rows, 32)),
                    dtype=jnp.float32)
    ref = np.asarray(csr_spmm_ref(g.rowptr, g.colidx, g.values, X))
    op = make_accel_spmm(g, backend="blocked")
    np.testing.assert_allclose(np.asarray(op(X)), ref, atol=1e-3, rtol=1e-3)
