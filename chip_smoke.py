#!/usr/bin/env python3
"""Smoke run of the served GCN path on a TPU, checked against float32 math.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # the four-chip fleet path, and only it

Phase A serves a 3-layer GCN (128 -> 256 -> 256 -> 40: ogbn-arxiv's feature
width and class count, with the hidden width of OGB's GCN baseline) over
the Arxiv analogue at its published size (169,343 nodes, 1,166,243 edges).
The dense ``H @ W`` runs on the device between aggregations; every
aggregation ``A' @ (H W)`` goes through ``GraphServeEngine.submit()`` ->
``spmm_batched`` -> ``route_spmm`` -> a compiled Pallas kernel (``hbm`` at
this size). Phase B sends one flush of small graphs (sum of rows <= 4096,
``resident``) and one of medium graphs (4096 < sum <= 16384, ``windowed``).

``--chips 4`` serves the Arxiv analogue through the whole-mesh path that
``route_fleet`` picks and a few small graphs placed across the devices,
and compares every answer with a one-chip ``GraphServeEngine`` in this
process.

Everything runs in this one process, with random weights and features made
from ``--seed``. Without a TPU the script exits non-zero before any work.
Every phase must pass; the last line of standard output is one JSON object
naming the device. Times printed on the way are informational.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core.graph import gcn_normalize                  # noqa: E402
from repro.data.graphs import (                             # noqa: E402
    make_benchmark_graph, make_power_law_graph,
)
from repro.kernels.ref import csr_spmm_ref                  # noqa: E402
from repro.kernels.spmm_accel import (                      # noqa: E402
    spmm_block_slabs, spmm_block_slabs_windowed,
)
from repro.kernels.spmm_hbm import spmm_block_slabs_hbm     # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.fleet import FleetGraphEngine              # noqa: E402
from repro.serve.graph_engine import (                      # noqa: E402
    GraphRequest, GraphServeEngine,
)

GRAPH = "Arxiv"
WIDTHS = (128, 256, 256, 40)
FORWARD_PASSES = 3
# Limit on max|served - reference| / max|reference|. Both sides are float32
# with HIGHEST-precision matmuls; they differ only in summation order (slab
# one-hot matmuls and cross-block folds against a per-row segment sum). That
# order alone gives 2.2e-5 after Phase A's three layers, the same on a v5e
# and on the CPU, and 1e-6 to 5e-6 for one aggregation of a small graph.
# Any bf16 rounding on the way (1 part in 256) or a wrong row lands far above.
REL_TOL = 1e-4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{name}: non-finite values in the answer")
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want))) / max(scale, 1e-30)
    log(f"{name}: shape {got.shape}, max|diff|/max|ref| = {err:.3e} "
        f"(limit {REL_TOL:g})")
    if not err <= REL_TOL:
        raise AssertionError(f"{name}: error {err:.3e} over {REL_TOL:g}")


@jax.jit
def dense(h, w):
    return jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST)


def gcn_weights(seed: int):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=(fi, fo)) * np.sqrt(2.0 / (fi + fo)),
                        jnp.float32)
            for fi, fo in zip(WIDTHS[:-1], WIDTHS[1:])]


def features(n: int, f: int, seed: int):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, f)), jnp.float32)


def served_forward(engine, gid: str, x, weights):
    """Logits and the kernel each aggregation was routed to."""
    h, routed = x, []
    for i, w in enumerate(weights):
        h = engine.submit(gid, dense(h, w)).result()
        routed.append(engine.last_decision.backend)
        if i < len(weights) - 1:
            h = jax.nn.relu(h)
    return jax.block_until_ready(h), routed


def reference_forward(g, x, weights):
    h = x
    for i, w in enumerate(weights):
        h = csr_spmm_ref(g.rowptr, g.colidx, g.values, dense(h, w))
        if i < len(weights) - 1:
            h = jax.nn.relu(h)
    return jax.block_until_ready(h)


def kernels_are_compiled() -> None:
    """Each SpMM entry point lowers to a Mosaic kernel on this platform,
    not to the interpreter's plain HLO."""
    B, C, R, N, F = 8, 256, 64, 64, 128
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((B, C), jnp.int32), ((B, C), jnp.float32), ((B, C), jnp.int32),
        ((B, R), jnp.int32), ((N, F), jnp.float32))]
    for name, fn in (("resident", spmm_block_slabs),
                     ("windowed", spmm_block_slabs_windowed),
                     ("hbm", spmm_block_slabs_hbm)):
        text = jax.jit(fn, static_argnames=("n_rows",)).lower(
            *shapes, n_rows=N).as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name} kernel lowers without Mosaic")
    log("resident, windowed and hbm kernels lower to Mosaic "
        "tpu_custom_call (compiled, not interpreted)")


def arxiv(seed: int):
    t0 = time.perf_counter()
    g = gcn_normalize(make_benchmark_graph(GRAPH, seed)[0])
    log(f"{GRAPH} analogue: {g.n_rows} nodes, {g.nnz} nnz after "
        f"gcn_normalize ({time.perf_counter() - t0:.2f} s on the host)")
    return g


def small_graphs(sizes, seed: int):
    return [gcn_normalize(make_power_law_graph(n, 8 * n, seed=seed + i))
            for i, n in enumerate(sizes)]


def phase_a(seed: int) -> None:
    g = arxiv(seed)
    engine = GraphServeEngine(backend="auto")
    t0 = time.perf_counter()
    plan = engine.register_graph(GRAPH, g)
    log(f"phase A: plan built in {time.perf_counter() - t0:.2f} s on the "
        f"host: {plan.num_blocks} blocks of C={plan.slabs['C']}, "
        f"R={plan.slabs['R']}")
    x = features(g.n_cols, WIDTHS[0], seed)
    weights = gcn_weights(seed)
    walls = []
    for p in range(FORWARD_PASSES):
        t0 = time.perf_counter()
        logits, routed = served_forward(engine, GRAPH, x, weights)
        walls.append(time.perf_counter() - t0)
        log(f"phase A: forward {p}: {walls[-1]:.3f} s wall"
            f"{' (includes compiles)' if p == 0 else ''}; "
            f"routed kernels per layer: {routed}")
        if routed != ["hbm"] * len(weights):
            raise AssertionError(f"phase A routed {routed}, expected hbm")
    st = engine.stats()
    log(f"phase A: {st['requests_served']} aggregations served, "
        f"routed_hbm={st['routed_hbm']}, padded/live blocks = "
        f"{st['padded_blocks']}/{st['live_blocks']}; first forward minus "
        f"the last: {walls[0] - walls[-1]:.3f} s (compile and warm-up)")
    engine.close()
    ref = reference_forward(g, x, weights)
    check("phase A logits vs float32 reference", logits, ref)


def phase_b(seed: int) -> None:
    engine = GraphServeEngine(backend="auto")
    mixes = {"resident": (520, 600, 640, 700, 760),
             "windowed": (2300, 2500, 2700, 2900)}
    for want, sizes in mixes.items():
        graphs = small_graphs(sizes, seed + (0 if want == "resident" else 50))
        reqs = []
        for i, g in enumerate(graphs):
            gid = f"{want}-{i}"
            engine.register_graph(gid, g)
            reqs.append(GraphRequest(gid, features(g.n_cols, WIDTHS[1],
                                                   seed + i)))
        before = engine.stats()
        t0 = time.perf_counter()
        engine.serve(reqs)
        wall = time.perf_counter() - t0
        after = engine.stats()
        routed = {k: after[f"routed_{k}"] - before[f"routed_{k}"]
                  for k in ("resident", "windowed", "hbm", "blocked")}
        log(f"phase B: {len(graphs)} graphs, {sum(sizes)} rows in one "
            f"flush -> dispatches by kernel {routed} "
            f"({wall:.3f} s wall, includes compiles)")
        if routed[want] != 1 or sum(routed.values()) != 1:
            raise AssertionError(f"phase B {want}: routed {routed}")
        for r, g in zip(reqs, graphs):
            check(f"phase B {r.graph_id}", r.out,
                  csr_spmm_ref(g.rowptr, g.colidx, g.values, r.x))
    engine.close()


def four_chips(seed: int) -> None:
    g = arxiv(seed)
    fleet = FleetGraphEngine(backend="auto")
    single = GraphServeEngine(backend="auto")
    log(f"four chips: fleet over {fleet.n_devices} devices; one-chip "
        f"engine on the default device")
    for eng in (fleet, single):
        eng.register_graph(GRAPH, g)
    x = features(g.n_cols, WIDTHS[1], seed)
    for p in range(2):
        t0 = time.perf_counter()
        got = fleet.submit(GRAPH, x).result()
        log(f"four chips: {GRAPH} pass {p}: {time.perf_counter() - t0:.3f} s "
            f"wall{' (includes compiles)' if p == 0 else ''}")
    fd = fleet.last_fleet_decision
    log(f"four chips: {GRAPH} routed {fd.describe() if fd else 'single'}")
    if fd is None or fd.strategy == "single":
        raise AssertionError(f"{GRAPH} did not take a whole-mesh path")
    check(f"four chips {GRAPH} vs one chip", got,
          single.submit(GRAPH, x).result())

    graphs = small_graphs((400, 520, 640, 760, 880, 1000, 1120, 1240),
                          seed + 100)
    reqs, one = [], []
    for i, sg in enumerate(graphs):
        gid = f"small-{i}"
        for eng in (fleet, single):
            eng.register_graph(gid, sg)
        xi = features(sg.n_cols, WIDTHS[1], seed + i)
        reqs.append(GraphRequest(gid, xi))
        one.append(GraphRequest(gid, xi))
    fleet.serve(reqs)
    single.serve(one)
    for r, o in zip(reqs, one):
        check(f"four chips {r.graph_id} vs one chip", r.out, o.out)

    st = fleet.stats()
    plans = st["cache_shard_sizes"]
    dispatches = st["fleet_device_dispatches"]
    log(f"four chips: plans per device {plans}; dispatches per device "
        f"{dispatches}; block-sharded blocks per device "
        f"{st.get('fleet_block_counts')}")
    for what, counts in (("plans", plans), ("dispatches", dispatches)):
        if int(np.count_nonzero(np.asarray(counts))) < 2:
            raise AssertionError(f"every one of the {what} landed on one "
                                 f"device: {counts}")
    fleet.close()
    single.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip fleet path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    log(f"{len(devices)} x {devices[0].device_kind}; compile cache at "
        f"{enable_compile_cache()}")

    t0 = time.perf_counter()
    kernels_are_compiled()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        phase_a(args.seed)
        phase_b(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
