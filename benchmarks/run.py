"""Benchmark harness entry point — one section per paper table/figure.

Emits ``name,us_per_call,derived`` CSV. Sections:
  fig5      overall SpMM comparison on the 18 Table-I graph analogues
  fig6      runtime vs RHS column dimension (16..128 + odd widths)
  table2    block-vs-warp partition + combined-warp ablations
  preproc   O(n) preprocessing scaling (paper §III-C)
  repair    streaming-update plan repair vs full rebuild at 0.1/1/10% nnz
            deltas (merges a "repair" key into
            benchmarks/results/serve_stats.json; nightly gates the 0.1%
            speedup >= 3x)
  serve     plan-cache amortization + batched multi-graph dispatch, plus
            the concurrent-submitter section (N threads of open-loop
            traffic: continuous-batching scheduler vs per-call dispatch;
            stats also land in benchmarks/results/serve_stats.json)
  routing   resident vs windowed vs HBM-gather vs auto at the VMEM
            boundaries (mixes that straddle the routing thresholds), and
            the resident kernel's block_major vs ft_major grid orders
  fleet     multi-device serving: FleetGraphEngine vs the single-device
            scheduler on the concurrent mix, plus the block-sharded giant
            graph with per-device balance (merges a "fleet" key into
            benchmarks/results/serve_stats.json; run with
            XLA_FLAGS=--xla_force_host_platform_device_count=8)
  multihost cross-host serving: a two-subprocess CPU fleet (REAL
            multi-process jax) routed by the placement directory —
            forwarded traffic + the collective global-mesh giant (merges
            a "multihost" key into benchmarks/results/serve_stats.json)
  tune      online partition autotuner: offline candidate ranking, the
            live shadow-measured promotion loop (steady-state tuned vs
            default dispatch), and the shadow p99-overhead check (merges
            a "tuning" key into benchmarks/results/serve_stats.json)
  sample    neighbor-sampling service: zipf seed-stream frontier hit rate,
            sampled-path throughput, full-fanout exactness vs the full
            graph on both backends, and the two-subprocess partitioned
            store with cross-partition frontier exchange (merges a
            "sampling" key into benchmarks/results/serve_stats.json;
            nightly gates with --require-sampling)
  moe       beyond-paper: block dispatch for MoE
  roofline  summary rows from the dry-run results (if present)
"""
from __future__ import annotations

import argparse
import json
import os


def _roofline_rows():
    from .common import csv_row
    path = os.path.join(os.path.dirname(__file__), "results", "dryrun.json")
    rows = []
    if not os.path.exists(path):
        return [csv_row("roofline/missing", 0.0, "run repro.launch.dryrun first")]
    with open(path) as f:
        for rec in json.load(f):
            cell = f"{rec['arch']}x{rec['shape']}"
            if "skipped" in rec:
                rows.append(csv_row(f"roofline/{cell}", 0.0,
                                    f"skipped={rec['skipped']}"))
                continue
            if "error" in rec:
                rows.append(csv_row(f"roofline/{cell}", 0.0,
                                    f"ERROR={rec['error'][:80]}"))
                continue
            rl = rec.get("roofline")
            if rl:
                dom = rl["bottleneck"]
                rows.append(csv_row(
                    f"roofline/{cell}", rl[dom + "_s"] * 1e6,
                    f"bottleneck={dom};compute_s={rl['compute_s']:.4g};"
                    f"memory_s={rl['memory_s']:.4g};"
                    f"collective_s={rl['collective_s']:.4g};"
                    f"useful={rl['useful_ratio']:.3f}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig5,fig6,table2,preproc,repair,"
                         "serve,routing,fleet,multihost,tune,sample,moe,"
                         "roofline")
    ap.add_argument("--budget-edges", type=int, default=200_000)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # multihost and sample spawn their own 2-process fleets, so they are
    # opt-in (not part of the default sweep: nightly CI runs them
    # explicitly)
    want = set(args.only.split(",")) if args.only else \
        {"fig5", "fig6", "table2", "preproc", "repair", "serve", "routing",
         "fleet", "tune", "moe", "roofline"}

    print("name,us_per_call,derived")
    if "fig5" in want:
        from .fig5_overall import run as fig5
        for r in fig5(budget_edges=args.budget_edges):
            print(r)
    if "fig6" in want:
        from .fig6_coldim import run as fig6
        for r in fig6(budget_edges=args.budget_edges):
            print(r)
    if "table2" in want:
        from .table2_ablation import run as t2
        for r in t2(budget_edges=args.budget_edges):
            print(r)
    if "preproc" in want:
        from .preprocessing import run as pp
        for r in pp():
            print(r)
    if "repair" in want:
        from .preprocessing import run_repair
        for r in run_repair():
            print(r)
    if "serve" in want:
        from .serve_graphs import run as serve
        for r in serve(budget_edges=args.budget_edges):
            print(r)
    if "routing" in want:
        from .spmm_routing import run as routing
        for r in routing(budget_edges=args.budget_edges):
            print(r)
    if "fleet" in want:
        from .fleet_serve import run as fleet
        for r in fleet(budget_edges=args.budget_edges):
            print(r)
    if "multihost" in want:
        from .multihost_serve import run as multihost
        for r in multihost(budget_edges=args.budget_edges):
            print(r)
    if "tune" in want:
        from .tune_partition import run as tune
        for r in tune(budget_edges=args.budget_edges):
            print(r)
    if "sample" in want:
        from .sampling_serve import run as sample
        for r in sample(budget_edges=args.budget_edges):
            print(r)
    if "moe" in want:
        from .moe_dispatch import run as moe
        for r in moe():
            print(r)
    if "roofline" in want:
        for r in _roofline_rows():
            print(r)


if __name__ == "__main__":
    main()
