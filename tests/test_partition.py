"""Property tests for the paper's Algorithms 1 & 2 (hypothesis)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.graph import csr_from_edges, degree_sort_csr
from repro.core.partition import (
    balance_stats, block_level_partition, get_partition_patterns,
    metadata_bytes, pack_slabs, validate_warp_nzs_override,
    warp_level_partition,
)

from conftest import make_powerlaw_csr


def _graph(n, seed, zipf=1.7):
    return degree_sort_csr(make_powerlaw_csr(n=n, seed=seed, zipf=zipf))


# ---------------------------------------------------------------------------
# Algorithm 1
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mbw,mwn", [(12, 32), (8, 16), (64, 4), (4, 64)])
def test_patterns_paper_invariants(mbw, mwn):
    p = get_partition_patterns(mbw, mwn, mode="paper")
    assert p.deg_bound == mbw * mwn
    # table covers 1 .. deg_bound INCLUSIVE: f*mwn >= d admits the boundary
    for d in range(1, p.deg_bound + 1):
        f, br, wn = int(p.factor[d]), int(p.block_rows[d]), int(p.warp_nzs[d])
        assert mbw % f == 0 and br == mbw // f          # factor divides warps
        assert f * mwn >= d                              # Algorithm 1 guard
        assert wn == -(-d // f)                          # ceil(d / factor)
        assert br * d <= p.deg_bound                     # block capacity bound
    # boundary degree: handled by the largest factor as ONE ordinary block
    assert int(p.factor[p.deg_bound]) == mbw
    assert int(p.block_rows[p.deg_bound]) == 1
    assert int(p.warp_nzs[p.deg_bound]) == mwn


@pytest.mark.parametrize("mode", ["paper", "tpu"])
def test_patterns_monotone_block_rows(mode):
    p = get_partition_patterns(16, 16, mode=mode)
    br = p.block_rows[1:]
    assert np.all(np.diff(br.astype(int)) <= 0)  # higher degree -> fewer rows


# ---------------------------------------------------------------------------
# Algorithm 2 invariants: every non-zero covered exactly once, in order
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(n=st.integers(10, 400), seed=st.integers(0, 10_000),
       mode=st.sampled_from(["paper", "tpu"]),
       mbw=st.sampled_from([4, 12, 32]), mwn=st.sampled_from([4, 16, 32]))
def test_partition_covers_all_nnz(n, seed, mode, mbw, mwn):
    g = _graph(n, seed)
    pats = get_partition_patterns(mbw, mwn, mode=mode)
    bp = block_level_partition(g, pats)
    # blocks tile the nnz range contiguously and exactly
    assert int(bp.nnz_blk.sum()) == g.nnz
    pos = 0
    for b in range(bp.num_blocks):
        assert int(bp.meta[b, 1]) == pos, "blocks must tile nnz contiguously"
        pos += int(bp.nnz_blk[b])
    # rows covered exactly once (non-split) / split rows only via one row id
    covered = np.zeros(g.n_rows, dtype=int)
    for b in range(bp.num_blocks):
        if bp.is_split[b]:
            continue
        r0, nr = int(bp.meta[b, 2]), int(bp.n_rows_blk[b])
        covered[r0:r0 + nr] += 1
    deg = np.diff(g.rowptr)
    bound = pats.deg_bound
    assert np.all(covered[(deg > 0) & (deg <= bound)] == 1)
    assert np.all(covered[deg == 0] == 0)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(20, 300), seed=st.integers(0, 1000))
def test_split_rows_capacity(n, seed):
    g = _graph(n, seed, zipf=1.3)  # heavier tail -> split rows likely
    pats = get_partition_patterns(4, 8, mode="paper")   # tiny bound = 32
    bp = block_level_partition(g, pats)
    assert np.all(bp.nnz_blk <= pats.deg_bound)
    # only degrees STRICTLY past the bound split (deg == bound is one
    # ordinary pattern block); split blocks of one row are consecutive and
    # sum to the row degree
    deg = np.diff(g.rowptr)
    for r in np.flatnonzero(deg > pats.deg_bound):
        blocks = np.flatnonzero((bp.meta[:, 2] == r) & bp.is_split)
        assert int(bp.nnz_blk[blocks].sum()) == deg[r]
        assert np.all(np.diff(blocks) == 1)
    for r in np.flatnonzero(deg == pats.deg_bound):
        assert not np.any((bp.meta[:, 2] == r) & bp.is_split)


# ---------------------------------------------------------------------------
# metadata economics (paper Eq. 1) + balance
# ---------------------------------------------------------------------------
def test_metadata_ratio_matches_eq1():
    g = _graph(2000, 3)
    pats = get_partition_patterns(12, 32, mode="paper")
    bp = block_level_partition(g, pats)
    wp = warp_level_partition(g, 32)
    ratio = metadata_bytes(bp) / metadata_bytes(wp)
    # Eq. 1: S_B/S_W ~= 1/avg_warps_per_block
    warps_per_block = wp.num_warps / bp.num_blocks
    assert ratio == pytest.approx(1.0 / warps_per_block, rel=1e-6)
    assert ratio < 0.5  # block-level metadata is much smaller


def test_balance_tpu_mode_beats_warp_level():
    g = _graph(3000, 4)
    pats = get_partition_patterns(256, 1, mode="tpu", max_rows_per_block=64)
    bp = block_level_partition(g, pats)
    wp = warp_level_partition(g, 32)
    bs, ws = balance_stats(bp), balance_stats(wp)
    assert bs["metadata_bytes"] < ws["metadata_bytes"]


@pytest.mark.parametrize("mode", ["paper", "tpu"])
def test_boundary_degree_pattern_path_and_kernel_parity(mode):
    """Rows with deg in {bound-1, bound, bound+1}: exactly-bound rows take
    the pattern path (single block, slab filled to capacity), only
    bound+1 splits — and both kernel backends agree with the dense oracle
    across the boundary."""
    import jax.numpy as jnp
    from repro.kernels.ops import spmm_blocked, spmm_pallas

    mbw, mwn = 4, 8
    bound = mbw * mwn                      # 32
    degs = [bound - 1, bound, bound + 1, bound, 3]   # mixed boundary classes
    n = max(degs) + 2                      # enough distinct columns per row
    src = np.concatenate([np.full(d, r) for r, d in enumerate(degs)])
    dst = np.concatenate([np.arange(d) for d in degs])
    rng = np.random.default_rng(0)
    g = degree_sort_csr(csr_from_edges(
        src, dst, n, values=rng.normal(size=len(src)).astype(np.float32)))

    pats = get_partition_patterns(mbw, mwn, mode=mode)
    bp = block_level_partition(g, pats)
    deg = np.diff(g.rowptr)
    for r in np.flatnonzero(deg == bound):
        mine = np.flatnonzero(bp.meta[:, 2] == r)
        # ONE ordinary block, not split, slab filled exactly to capacity
        own = [b for b in mine if not bp.is_split[b]
               and r < bp.meta[b, 2] + bp.n_rows_blk[b]]
        assert len(own) == 1 and not bp.is_split[own[0]]
        assert int(bp.nnz_blk[own[0]]) == bound
    for r in np.flatnonzero(deg == bound + 1):
        blocks = np.flatnonzero((bp.meta[:, 2] == r) & bp.is_split)
        assert len(blocks) == 2            # bound + 1 nzs -> two split blocks
    assert np.all(bp.is_split[bp.meta[:, 0] <= bound] == False)  # noqa: E712

    # parity through pack_slabs and BOTH kernel backends vs dense oracle
    slabs = pack_slabs(g, bp)
    x = jnp.asarray(rng.normal(size=(g.n_cols, 8)), jnp.float32)
    ref = g.to_dense() @ np.asarray(x)
    out_blocked = spmm_blocked(
        jnp.asarray(slabs["colidx"]), jnp.asarray(slabs["values"]),
        jnp.asarray(slabs["rowloc"]), jnp.asarray(slabs["out_row"]),
        x, g.n_rows)
    np.testing.assert_allclose(np.asarray(out_blocked), ref,
                               atol=1e-4, rtol=1e-4)
    jslabs = {k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
              for k, v in slabs.items()}
    out_pallas = spmm_pallas(jslabs, x, g.n_rows)
    np.testing.assert_allclose(np.asarray(out_pallas), ref,
                               atol=1e-4, rtol=1e-4)


def test_pack_slabs_every_nz_exactly_once():
    g = _graph(500, 7)
    pats = get_partition_patterns(32, 8, mode="tpu")
    bp = block_level_partition(g, pats)
    slabs = pack_slabs(g, bp)
    assert float(slabs["values"].sum()) == pytest.approx(float(g.values.sum()), rel=1e-5)
    # padded slots must carry zero values
    nnzs = bp.nnz_blk
    for b in range(min(bp.num_blocks, 50)):
        assert np.all(slabs["values"][b, nnzs[b]:] == 0)


# ---------------------------------------------------------------------------
# warp_nzs overrides (the autotuner's candidate axis): any ADMISSIBLE
# table yields bit-identical SpMM output on both kernel backends, and
# inadmissible tables are rejected up front
# ---------------------------------------------------------------------------
def _int_graph(n, seed):
    """Small-integer-valued graph: SpMM sums are exactly representable in
    float32, so different block partitions must agree BIT-identically."""
    rng = np.random.default_rng(seed)
    deg = np.minimum(rng.zipf(1.6, n), 200)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, len(src))
    vals = rng.integers(1, 4, len(src)).astype(np.float32)
    return degree_sort_csr(csr_from_edges(src, dst, n, values=vals))


def _random_admissible_override(mbw, mwn, seed):
    rng = np.random.default_rng(seed)
    lo = np.maximum(1, -(-np.arange(1, mbw * mwn + 1) // mbw))  # ceil(d/mbw)
    return rng.integers(lo, mwn + 1)


@settings(max_examples=12, deadline=None)
@given(n=st.integers(30, 250), seed=st.integers(0, 10_000),
       mode=st.sampled_from(["paper", "tpu"]),
       dims=st.sampled_from([(4, 4), (8, 2), (4, 8)]))
def test_admissible_override_bit_identical_on_both_backends(n, seed, mode,
                                                            dims):
    import jax.numpy as jnp
    from repro.kernels.ops import spmm_blocked, spmm_pallas

    mbw, mwn = dims
    g = _int_graph(n, seed)
    override = _random_admissible_override(mbw, mwn, seed + 1)
    rng = np.random.default_rng(seed + 2)
    x = jnp.asarray(rng.integers(-2, 3, (g.n_cols, 6)), jnp.float32)
    ref = (g.to_dense().astype(np.float64)
           @ np.asarray(x, np.float64)).astype(np.float32)

    for ovr in (None, override):
        pats = get_partition_patterns(mbw, mwn, mode=mode,
                                      warp_nzs_override=ovr)
        bp = block_level_partition(g, pats)
        slabs = pack_slabs(g, bp)
        out_b = spmm_blocked(
            jnp.asarray(slabs["colidx"]), jnp.asarray(slabs["values"]),
            jnp.asarray(slabs["rowloc"]), jnp.asarray(slabs["out_row"]),
            x, g.n_rows)
        np.testing.assert_array_equal(np.asarray(out_b), ref)
        jslabs = {k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
                  for k, v in slabs.items()}
        out_p = spmm_pallas(jslabs, x, g.n_rows)
        np.testing.assert_array_equal(np.asarray(out_p), ref)


@pytest.mark.parametrize("mode", ["paper", "tpu"])
def test_override_of_all_max_warp_nzs_is_the_default_table(mode):
    mbw, mwn = 8, 4
    default = get_partition_patterns(mbw, mwn, mode=mode)
    same = get_partition_patterns(
        mbw, mwn, mode=mode,
        warp_nzs_override=np.full(mbw * mwn, mwn))
    for field in ("factor", "block_rows", "warp_nzs"):
        np.testing.assert_array_equal(getattr(default, field),
                                      getattr(same, field))


def test_inadmissible_overrides_rejected():
    mbw, mwn = 4, 8
    bound = mbw * mwn
    ok = np.full(bound, mwn)
    validate_warp_nzs_override(mbw, mwn, ok)            # sanity: passes
    bad_low = ok.copy()
    bad_low[0] = 0                                       # below 1
    with pytest.raises(ValueError, match="degree"):
        validate_warp_nzs_override(mbw, mwn, bad_low)
    bad_high = ok.copy()
    bad_high[3] = mwn + 1                                # above max_warp_nzs
    with pytest.raises(ValueError, match="degree"):
        validate_warp_nzs_override(mbw, mwn, bad_high)
    bad_cover = ok.copy()
    bad_cover[bound - 1] = mwn - 1      # mbw * (mwn-1) < bound: row uncovered
    with pytest.raises(ValueError, match="degree"):
        validate_warp_nzs_override(mbw, mwn, bad_cover)
    with pytest.raises(ValueError, match="length"):
        validate_warp_nzs_override(mbw, mwn, ok[:-1])
    with pytest.raises(ValueError, match="integer"):
        validate_warp_nzs_override(mbw, mwn, ok.astype(np.float32) + 0.5)
    # the same guard fires through the pattern-builder entry point
    with pytest.raises(ValueError):
        get_partition_patterns(mbw, mwn, warp_nzs_override=bad_cover)
