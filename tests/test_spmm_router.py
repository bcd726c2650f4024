"""VMEM-budget routing: regime boundaries, the forced-resident guard, and
kernel parity across the three regimes (windowed exercised with a small
window so the middle regime stays CI-cheap)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import gcn_normalize
from repro.core.plan_cache import PartitionConfig, build_partition_plan
from repro.core.spmm import make_accel_spmm
from repro.kernels import ops as kops
from repro.kernels.router import (
    MAX_WINDOWS,
    VmemBudgetError,
    assert_resident_fits,
    estimate_vmem_bytes,
    pad_rows,
    resident_window_rows,
    route_spmm,
)
from repro.kernels.spmm_accel import (
    spmm_block_slabs,
    spmm_block_slabs_windowed,
)

from conftest import make_powerlaw_csr

C, R = 256, 64
WINDOW = resident_window_rows()          # 4096 at f32/128-lane defaults


def test_default_window_is_documented_4096():
    assert WINDOW == 4096


# --------------------------------------------------------------- boundaries
def test_route_exact_resident_boundary():
    assert route_spmm(WINDOW, 64, C, R).backend == "resident"
    assert route_spmm(WINDOW + 1, 64, C, R).backend == "windowed"


def test_route_exact_windowed_boundary():
    hi = MAX_WINDOWS * WINDOW
    d = route_spmm(hi, 64, C, R)
    assert d.backend == "windowed" and d.num_windows == MAX_WINDOWS
    d = route_spmm(hi + 1, 64, C, R)
    assert d.backend == "hbm" and d.num_windows == 0


def test_route_respects_row_padding():
    # 4090 unpadded rows pad to 4096 -> still resident; 4092 pads to 4096
    # too; 4097 pads to 4104 -> windowed.
    assert route_spmm(4090, 64, C, R).n_pad == 4096
    assert route_spmm(4090, 64, C, R).backend == "resident"
    assert route_spmm(4097, 64, C, R).backend == "windowed"


def test_route_itemsize_scales_boundary():
    # bf16 halves the per-row cost -> twice the resident rows.
    assert resident_window_rows(itemsize=2) == 2 * WINDOW
    assert route_spmm(2 * WINDOW, 64, C, R, itemsize=2).backend == "resident"
    assert route_spmm(2 * WINDOW + 8, 64, C, R, itemsize=2).backend == "windowed"


def test_route_custom_budget():
    # Shrinking the budget moves every boundary proportionally.
    small = 64 * 1024
    w = resident_window_rows(budget_bytes=small)
    assert w == small // (128 * 4) // 8 * 8
    assert route_spmm(w, 16, C, R, budget_bytes=small).backend == "resident"
    assert route_spmm(w + 1, 16, C, R, budget_bytes=small).backend == "windowed"
    assert route_spmm(MAX_WINDOWS * w + 1, 16, C, R,
                      budget_bytes=small).backend == "hbm"


def test_vmem_estimate_ordering():
    n_pad = pad_rows(20_000)
    resident = estimate_vmem_bytes("resident", n_pad, C, R)
    windowed = estimate_vmem_bytes("windowed", n_pad, C, R)
    hbm = estimate_vmem_bytes("hbm", n_pad, C, R)
    assert resident > windowed > hbm
    # hbm footprint is independent of N
    assert hbm == estimate_vmem_bytes("hbm", 8, C, R)
    with pytest.raises(ValueError, match="unknown backend"):
        estimate_vmem_bytes("nope", n_pad, C, R)


def test_decision_reports_estimates():
    d = route_spmm(20_000, 64, C, R)
    assert d.backend == "hbm"
    assert d.resident_bytes > d.budget_bytes
    assert d.vmem_bytes < d.budget_bytes
    assert "hbm" in d.describe()


def test_hbm_estimate_counts_the_gather_width():
    """An hbm step holds a [C, W] slab and a double-buffered [R, W] output
    block at W = hbm_gather_width(F_pad), and the decision carries W."""
    d = route_spmm(20_000, 256, C, R)
    assert d.backend == "hbm" and d.gather_width == 256
    narrow = estimate_vmem_bytes("hbm", d.n_pad, C, R)       # W = 128
    assert d.vmem_bytes - narrow == (C + 2 * R) * 128 * 4
    assert route_spmm(20_000, 2176, C, R).gather_width == 128


def test_oversized_block_capacity_falls_back_then_raises():
    """The MXU operands scale with C*R in EVERY regime: a partition capacity
    that pushes the resident step over the total budget must route to hbm
    (leaner X cost) even for small N, and one that overflows hbm too must
    raise rather than hand hardware an uncompilable step."""
    d = route_spmm(4_000, 64, 2048, 768)   # one-hot alone is 6 MiB
    assert d.backend == "hbm" and "total VMEM budget" in d.reason
    assert d.vmem_bytes <= d.total_budget_bytes
    with pytest.raises(VmemBudgetError, match="no SpMM regime"):
        route_spmm(100, 64, 4096, 1024)    # one-hot alone is 16 MiB


def test_every_routed_regime_fits_total_budget():
    """budget_bytes caps the per-buffer X tile; the whole-step footprint of
    whatever regime routing picks must fit the total VMEM budget — the
    uniform invariant serving asserts per dispatch (windowed's two in-flight
    windows exceed the X-tile slice by design, never the total)."""
    for n in [64, WINDOW, WINDOW + 8, 3 * WINDOW, MAX_WINDOWS * WINDOW + 8,
              500_000]:
        d = route_spmm(n, 64, C, R)
        assert d.vmem_bytes <= d.total_budget_bytes, (n, d.backend)
        if d.backend == "resident":
            assert n <= d.window_rows


# -------------------------------------------------------------------- guard
def test_assert_resident_fits_message_names_dims_and_fallback():
    with pytest.raises(VmemBudgetError) as ei:
        assert_resident_fits(20_000, 64, C, R)
    msg = str(ei.value)
    assert "N_pad=20000" in msg and "C=256" in msg and "R=64" in msg
    assert "hbm" in msg          # the suggested backend for this shape
    # middle regime suggests the windowed kernel instead
    with pytest.raises(VmemBudgetError, match="windowed"):
        assert_resident_fits(5_000, 64, C, R)


def test_spmm_block_slabs_guard_raises_not_compiles():
    """The resident kernel itself refuses an oversized X at trace time."""
    slabs = {
        "colidx": jnp.zeros((1, 8), jnp.int32),
        "values": jnp.zeros((1, 8), jnp.float32),
        "rowloc": jnp.zeros((1, 8), jnp.int32),
        "out_row": jnp.zeros((1, 4), jnp.int32),
    }
    x = jnp.zeros((WINDOW + 8, 4), jnp.float32)
    with pytest.raises(VmemBudgetError, match="VMEM budget"):
        spmm_block_slabs(slabs["colidx"], slabs["values"], slabs["rowloc"],
                         slabs["out_row"], x, 4)
    # one row under the boundary still runs
    out = spmm_block_slabs(slabs["colidx"], slabs["values"], slabs["rowloc"],
                           slabs["out_row"], jnp.zeros((WINDOW, 4)), 4)
    assert out.shape == (4, 4)


# ------------------------------------------------------------ kernel parity
@pytest.mark.parametrize("window_rows,F", [(64, 32), (64, 130), (96, 17)])
def test_windowed_kernel_matches_resident(window_rows, F):
    """Small windows force multi-window accumulation on a CI-size graph."""
    g = gcn_normalize(make_powerlaw_csr(n=220, seed=7, zipf=1.5))
    plan = build_partition_plan(g, PartitionConfig())
    x = jnp.asarray(np.random.default_rng(7).normal(size=(g.n_cols, F)),
                    jnp.float32)
    ref = spmm_block_slabs(plan.slabs["colidx"], plan.slabs["values"],
                           plan.slabs["rowloc"], plan.slabs["out_row"],
                           x, plan.n_rows)
    out = spmm_block_slabs_windowed(
        plan.slabs["colidx"], plan.slabs["values"], plan.slabs["rowloc"],
        plan.slabs["out_row"], x, plan.n_rows, window_rows=window_rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_windowed_single_window_degenerate():
    g = gcn_normalize(make_powerlaw_csr(n=60, seed=8))
    plan = build_partition_plan(g, PartitionConfig())
    x = jnp.asarray(np.random.default_rng(8).normal(size=(g.n_cols, 12)),
                    jnp.float32)
    ref = spmm_block_slabs(plan.slabs["colidx"], plan.slabs["values"],
                           plan.slabs["rowloc"], plan.slabs["out_row"],
                           x, plan.n_rows)
    out = spmm_block_slabs_windowed(
        plan.slabs["colidx"], plan.slabs["values"], plan.slabs["rowloc"],
        plan.slabs["out_row"], x, plan.n_rows)   # default window >> N
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_spmm_auto_small_graph_picks_resident():
    g = gcn_normalize(make_powerlaw_csr(n=120, seed=9))
    plan = build_partition_plan(g, PartitionConfig())
    x = jnp.asarray(np.random.default_rng(9).normal(size=(g.n_cols, 8)),
                    jnp.float32)
    out, decision = kops.spmm_auto(plan.slabs, x, plan.n_rows,
                                   return_decision=True)
    assert decision.backend == "resident"
    ref = kops.spmm_pallas(plan.slabs, x, plan.n_rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0)


@pytest.mark.parametrize("backend", ["auto", "windowed", "hbm"])
def test_accel_spmm_new_backends_agree(backend):
    g = gcn_normalize(make_powerlaw_csr(n=150, seed=10))
    x = jnp.asarray(np.random.default_rng(10).normal(size=(g.n_cols, 24)),
                    jnp.float32)
    op = make_accel_spmm(g, backend="blocked")
    ref = np.asarray(op(x))
    out = np.asarray(op(x, backend=backend))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_hbm_kernel_wide_features_multi_tile():
    """F > 128 spans several feature tiles: each HBM grid step must DMA its
    OWN lane window (regression: the gather once copied full-width rows into
    a one-tile buffer, crashing for any F_pad > f_tile)."""
    g = gcn_normalize(make_powerlaw_csr(n=140, seed=12))
    x = jnp.asarray(np.random.default_rng(12).normal(size=(g.n_cols, 200)),
                    jnp.float32)
    op = make_accel_spmm(g, backend="blocked")
    np.testing.assert_allclose(np.asarray(op(x, backend="hbm")),
                               np.asarray(op(x)), atol=1e-5, rtol=1e-5)
