"""Backend-routing benchmark: resident vs windowed vs HBM-gather vs auto.

Three graph mixes straddle the routing thresholds (f32 defaults: resident
N_pad <= 4096, windowed <= 4 x 4096, hbm beyond — see ``router.py``):

  resident_mix   several small graphs, concatenated features fit VMEM
  windowed_mix   mid-size graphs whose concatenation needs 2 row windows
  hbm_mix        one sparse huge-column graph (the web-scale shape) + smalls

For each mix, every *legal* backend is timed through the fused batched path
(``spmm_batched``), plus ``auto``, which should match the best legal choice.
Backends whose forced run would exceed the VMEM budget emit a
``raises=VmemBudgetError`` row instead of a timing — that raise (rather
than a silent oversized compile) is the contract under test. Timings are
interpret-mode CPU numbers: regime *rankings* here reflect emulation cost,
not TPU DMA behavior; the row to watch is auto vs its chosen backend
(routing overhead ~= 0).
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from repro.core.graph import csr_from_edges, gcn_normalize
from repro.core.plan_cache import PartitionConfig, build_partition_plan
from repro.kernels.router import VmemBudgetError, route_spmm
from repro.kernels.spmm_batched import spmm_batched

from .common import csv_row, time_call

BACKENDS = ["pallas", "windowed", "hbm", "auto"]


def _rand_graph(n_rows: int, n_cols: int, nnz: int, seed: int):
    rng = np.random.default_rng(seed)
    src = np.sort(rng.integers(0, n_rows, nnz))
    dst = rng.integers(0, n_cols, nnz)
    return gcn_normalize(csr_from_edges(src, dst, n_cols))


def _mixes(feat: int):
    """(name, [(n_rows, n_cols, nnz), ...]) mixes around the boundaries."""
    return [
        # sum n_cols = 2_400 -> resident (<= 4096)
        ("resident_mix", [(800, 800, 3_000)] * 3),
        # sum n_cols = 7_200 -> windowed, 2 windows (4096 < N <= 16384)
        ("windowed_mix", [(2_400, 2_400, 6_000)] * 3),
        # sum n_cols = 19_200: one huge sparse graph tips the batch -> hbm
        ("hbm_mix", [(600, 18_000, 2_000), (600, 600, 2_000),
                     (600, 600, 2_000)]),
    ]


def run(budget_edges: int = 200_000, feat: int = 32) -> List[str]:
    rows: List[str] = []
    cfg = PartitionConfig()
    rng = np.random.default_rng(0)
    scale = min(1.0, budget_edges / 200_000)

    for mix_name, shapes in _mixes(feat):
        plans, xs = [], []
        for i, (n_r, n_c, nnz) in enumerate(shapes):
            g = _rand_graph(n_r, n_c, max(200, int(nnz * scale)), seed=i)
            plans.append(build_partition_plan(g, cfg))
            xs.append(jnp.asarray(rng.normal(size=(g.n_cols, feat)),
                                  jnp.float32))
        n_cat = sum(int(x.shape[0]) for x in xs)
        decision = route_spmm(n_cat, feat, int(plans[0].slabs["C"]),
                              int(plans[0].slabs["R"]))

        for backend in BACKENDS:
            def call(backend=backend):
                return spmm_batched([p.slabs for p in plans], xs,
                                    [p.n_rows for p in plans],
                                    backend=backend)
            try:
                us = time_call(call, warmup=1, iters=3)
            except VmemBudgetError:
                rows.append(csv_row(
                    f"routing/{mix_name}_{backend}", 0.0,
                    f"raises=VmemBudgetError;n_cat={n_cat};"
                    f"budget_rows={decision.window_rows}"))
                continue
            if backend == "auto":
                note = (f"exec={decision.backend};"
                        f"vmem~{decision.vmem_bytes // 1024}KiB")
            else:
                note = f"exec={backend}"
            rows.append(csv_row(f"routing/{mix_name}_{backend}", us,
                                f"{note};n_cat={n_cat}"))
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for r in run():
        print(r)
