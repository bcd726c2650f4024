"""The three SpMM kernels compile for a TPU v5e at real shapes.

Nothing runs: each test lowers a kernel for one device of a described
``v5e:2x2`` topology and has the TPU compiler build it, so a kernel that
interpret mode accepts but Mosaic refuses (block tiling, vector gathers, DMA
addresses) fails here instead of on the chip. ``pallas_interpret`` is
steered inside each test; the topology is described only inside a fixture,
so collecting this file never loads the TPU runtime.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import spmm_accel, spmm_hbm

C, R = 256, 64   # default PartitionConfig slab capacity (core/partition.py)

# (kernel, B blocks, N feature rows, F[, H heads]): the Arxiv analogue at
# its published size (169,343 nodes; 6,397 blocks of the bounded graph)
# routes to the HBM kernel, which gathers at full width at the model's F=40
# and F=256 and tiles past the 2 MiB gather budget (F=2176: W=128, seventeen
# planes); a resident dispatch has N <= 4096, a windowed one 4096 < N <=
# 16384. The GAT analogue (16,384 padded blocks) reduces 3 heads of 256
# lanes (F=768) and of 128 (F=384), and 17 heads over 17 planes (F=2176).
CASES = {
    "resident": (spmm_accel.spmm_block_slabs, 256, 4096, 256),
    "windowed": (spmm_accel.spmm_block_slabs_windowed, 512, 12_000, 256),
    "hbm": (spmm_hbm.spmm_block_slabs_hbm, 6397, 169_343, 256),
    "hbm-F40": (spmm_hbm.spmm_block_slabs_hbm, 6397, 169_343, 40),
    "hbm-F2176": (spmm_hbm.spmm_block_slabs_hbm, 6397, 169_343, 2176),
    "resident-2heads": (spmm_accel.spmm_block_slabs, 256, 4096, 512, 2),
    "windowed-2heads": (spmm_accel.spmm_block_slabs_windowed, 512, 12_000,
                        512, 2),
    "hbm-3heads-F768": (spmm_hbm.spmm_block_slabs_hbm, 16_384, 169_343, 768,
                        3),
    "hbm-3heads-F384": (spmm_hbm.spmm_block_slabs_hbm, 16_384, 169_343, 384,
                        3),
    "hbm-17heads-F2176": (spmm_hbm.spmm_block_slabs_hbm, 6397, 169_343, 2176,
                          17),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    for mod in (spmm_accel, spmm_hbm):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, B, N, F, sharding, H=1):
    values = (B, C) if H == 1 else (B, H, C)
    # a fresh function to jit: JAX may reuse a trace of ``fn`` itself that
    # an earlier call made while the kernels were still interpreted
    return jax.jit(lambda *a: fn(*a, n_rows=N)).lower(
        sds((B, C), jnp.int32, sharding), sds(values, jnp.float32, sharding),
        sds((B, C), jnp.int32, sharding), sds((B, R), jnp.int32, sharding),
        sds((N, F), jnp.float32, sharding)).compile()


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip, no_compile_cache,
                                 compiled_kernels):
    fn, B, N, F, *H = CASES[kernel]
    text = _compile(fn, B, N, F, one_chip, *H).as_text()
    assert "tpu_custom_call" in text
    # the epilogue (spmm_accel.scatter_block_rows) is whole-array work: a
    # gather of fewer lanes than a row holds lowers to a loop of one-row
    # copies, 169,343 steps a call at F=40
    assert " while(" not in text


def test_edge_softmax_compiles_for_v5e(one_chip, no_compile_cache):
    """The per-call attention values of the GAT analogue, 3 heads over
    16,384 padded blocks, compile with under 1 GiB of temporaries: a gather
    of [H]-wide rows would pad H to 128 lanes, 2 GiB per operand."""
    from repro.kernels.edge_softmax import _edge_softmax_slabs
    B, H, N = 16_384, 3, 169_343
    shapes = [((B, C), jnp.int32), ((B, C), jnp.float32),
              ((B, C), jnp.int32), ((B, R), jnp.int32), ((B,), jnp.int32),
              ((B, H), jnp.int32), ((B, H), jnp.float32),
              ((N, H), jnp.float32), ((N + 1, H), jnp.float32)]
    compiled = _edge_softmax_slabs.lower(
        *(sds(s, d, one_chip) for s, d in shapes)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30, mem.temp_size_in_bytes


def test_cpu_trace_is_not_reused_for_the_tpu_compile(one_chip,
                                                     no_compile_cache,
                                                     monkeypatch):
    """The same kernel at the same shape, first interpreted on the CPU, then
    compiled for the chip: the second program must hold the Mosaic kernel,
    not the interpreter's trace of the first."""
    B, N, F = 8, 64, 128
    rng = np.random.default_rng(0)
    args = (rng.integers(0, N, (B, C)).astype(np.int32),
            rng.normal(size=(B, C)).astype(np.float32),
            rng.integers(0, R, (B, C)).astype(np.int32),
            np.tile(np.arange(R, dtype=np.int32), (B, 1)),
            rng.normal(size=(N, F)).astype(np.float32))
    jax.block_until_ready(spmm_accel.spmm_block_slabs(
        *map(jnp.asarray, args), N))
    monkeypatch.setattr(spmm_accel, "pallas_interpret", lambda: False)
    compiled = _compile(spmm_accel.spmm_block_slabs, B, N, F, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
