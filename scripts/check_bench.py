#!/usr/bin/env python
"""Benchmark acceptance + regression gate for nightly CI.

Reads a fresh ``benchmarks/results/serve_stats.json`` (produced by
``python -m benchmarks.run --only serve,routing,fleet[,repair,multihost]``)
and

* asserts the ABSOLUTE acceptance properties of the serving stack
  (cross-caller coalescing, fleet-vs-single coalescing, block-shard
  balance, zipf hot-plan replication, incremental plan repair >= 3x a
  full rebuild at 0.1% churn), and
* compares throughput rows against a COMMITTED baseline
  (``benchmarks/baselines/serve_stats.baseline.json``), failing on a
  >20% drop so perf regressions surface as red nightlies instead of
  silently compounding.

Parallel-hardware gates (fleet occupancy >= 0.75, replicated >= 1.3x
replication-disabled requests/s) only make sense where device launches
can actually overlap; on a single-core container XLA serializes every
dispatch, so those two gates are enforced when ``os.cpu_count() >= 4``
(the nightly runners) and reported informationally below that. The
structural replication gates — hot plan promoted to >= 2 replicas, its
dispatches spread across devices, and replicated occupancy >= 3x the
single-owner run — hold on any machine and are always enforced.

Exit code 0 = all gates pass; 1 = failure (messages on stderr).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(REPO, "benchmarks", "results", "serve_stats.json")
BASELINE = os.path.join(REPO, "benchmarks", "baselines",
                        "serve_stats.baseline.json")

# fresh-run throughput may drop this much vs the committed baseline
# before the gate trips (nightly runners are shared: some noise is life)
MAX_DROP = 0.20

# (json path, human name) of the throughput rows under regression watch
THROUGHPUT_ROWS = [
    (("scheduler", "requests_per_s"), "scheduler requests/s"),
    (("fleet", "single", "requests_per_s"), "single-device requests/s"),
    (("fleet", "fleet", "requests_per_s"), "fleet requests/s"),
    (("fleet", "zipf", "replicated", "requests_per_s"),
     "zipf replicated requests/s"),
]


def _get(d: Dict, path) -> object:
    for k in path:
        d = d[k]
    return d


class Gate:
    def __init__(self) -> None:
        self.failures: List[str] = []

    def check(self, ok: bool, msg: str) -> None:
        print(("PASS  " if ok else "FAIL  ") + msg)
        if not ok:
            self.failures.append(msg)

    def info(self, msg: str) -> None:
        print("INFO  " + msg)


def check_serving(g: Gate, s: Dict, *, parallel: bool) -> None:
    gpd = s["scheduler"]["graphs_per_dispatch"]
    g.check(gpd > 1.0, f"cross-caller coalescing: graphs_per_dispatch="
                       f"{gpd:.2f} > 1.0")

    fl = s["fleet"]
    gpr = fl["fleet"]["fleet_graphs_per_round"]
    single_gpd = fl["single"]["graphs_per_dispatch"]
    g.check(gpr >= single_gpd,
            f"fleet coalescing: graphs_per_round={gpr:.2f} >= "
            f"single graphs_per_dispatch={single_gpd:.2f}")

    nbs = fl["giant"]["block_sharded_dispatches"]
    g.check(nbs >= 1, f"giant graph block-sharded: dispatches={nbs} >= 1")
    bal = fl["giant"]["block_balance"]
    g.check(1.0 <= bal <= 1.10,
            f"block placement balance: {bal:.3f} within [1.0, 1.10]")

    # ---- zipf hot-plan replication ------------------------------------
    z = fl["zipf"]
    rep, dis = z["replicated"], z["disabled"]
    g.check(rep["promotions"] >= 1,
            f"hot-plan promotion fired: promotions={rep['promotions']}")
    g.check(rep["replica_copies"] >= 1,
            f"replica copies staged: {rep['replica_copies']}")
    disp = [d for d in rep["fleet_device_dispatches"] if d > 0]
    g.check(len(disp) >= 2,
            f"replicated dispatches spread over {len(disp)} devices (>= 2)")
    occ_r, occ_d = rep["fleet_occupancy"], dis["fleet_occupancy"]
    g.check(occ_r >= 3.0 * occ_d,
            f"replication lifts occupancy: {occ_r:.2f} >= 3x "
            f"single-owner {occ_d:.2f}")
    if parallel:
        g.check(occ_r >= 0.75,
                f"fleet occupancy under zipf mix: {occ_r:.2f} >= 0.75")
        g.check(z["speedup"] >= 1.3,
                f"replicated vs disabled speedup: {z['speedup']:.2f} >= 1.3x")
    else:
        g.info(f"single-core host (cpu_count={os.cpu_count()}): occupancy="
               f"{occ_r:.2f} speedup={z['speedup']:.2f} reported only — "
               f"launches cannot overlap without cores")


def check_repair(g: Gate, s: Dict) -> None:
    r = s.get("repair")
    if r is None:
        g.check(False, "repair section present in results "
                       "(run benchmarks with --only repair)")
        return
    sp = r["repair_speedup"]
    g.check(sp >= 3.0,
            f"incremental plan repair at 0.1% nnz churn: {sp:.2f}x >= 3x "
            f"over full rebuild")
    for key, frac in sorted(
            (k, k.split("_", 1)[1]) for k in r if k.startswith("frac_")):
        fr = r[key]
        g.check(fr["speedup"] >= 1.0 if fr["repaired"] else True,
                f"repair at {frac} churn never slower than rebuild: "
                f"{fr['speedup']:.2f}x (repaired={fr['repaired']})")


def check_multihost(g: Gate, s: Dict) -> None:
    mh = s.get("multihost")
    if mh is None:
        g.check(False, "multihost section present in results "
                       "(run benchmarks with --only multihost)")
        return
    hp = mh["host_placements"]
    g.check(len(hp) == 2 and all(c >= 1 for c in hp),
            f"directory spread plans across both hosts: {hp}")
    g.check(mh["forwarded"] >= 1,
            f"cross-host forwarding happened: forwarded={mh['forwarded']}")
    fo = sum(r["failovers"] for r in mh["per_rank"])
    g.check(fo == 0, f"no unexpected peer failovers: {fo}")
    bc = mh["block_counts"]
    g.check(bool(bc) and max(bc) - min(bc) <= 1,
            f"global block shard balanced: {bc}")


def check_sampling(g: Gate, s: Dict) -> None:
    sm = s.get("sampling")
    if sm is None:
        g.check(False, "sampling section present in results "
                       "(run benchmarks with --only sample)")
        return
    hr = sm["zipf_stream"]["hit_rate"]
    g.check(hr >= 0.5,
            f"zipf seed-stream frontier hit rate: {hr:.3f} >= 0.5")
    for backend, ex in sorted(sm["exactness"].items()):
        g.check(ex["exact"],
                f"full-fanout sampled inference bit-exact on {backend}: "
                f"max_abs_diff={ex['max_abs_diff']:.3g}")
    part = sm.get("partitioned")
    if part is None:
        g.check(False, "partitioned-store run present in sampling section")
        return
    g.check(part["parity"],
            "partitioned sampling matches the monolithic store "
            f"({part['processes']} processes, "
            f"{len(part['per_rank'])} ranks reporting)")
    g.check(part["remote_edges"] >= 1,
            f"cross-partition hops actually crossed the data plane: "
            f"remote_edges={part['remote_edges']}")
    g.check(part["failovers"] == 0,
            f"no frontier-exchange failovers: {part['failovers']}")


def check_regression(g: Gate, s: Dict, baseline_path: str) -> None:
    if not os.path.exists(baseline_path):
        g.check(False, f"baseline missing: {baseline_path}")
        return
    with open(baseline_path) as f:
        base = json.load(f)
    for path, name in THROUGHPUT_ROWS:
        try:
            b = float(_get(base, path))
        except (KeyError, TypeError):
            g.info(f"{name}: not in baseline, skipped")
            continue
        try:
            v = float(_get(s, path))
        except (KeyError, TypeError):
            g.check(False, f"{name}: missing from fresh results")
            continue
        floor = b * (1.0 - MAX_DROP)
        g.check(v >= floor,
                f"{name}: {v:.1f} >= {floor:.1f} "
                f"(baseline {b:.1f} - {MAX_DROP:.0%})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--results", default=RESULTS,
                    help="fresh serve_stats.json to gate")
    ap.add_argument("--baseline", default=BASELINE,
                    help="committed baseline to diff against")
    ap.add_argument("--require-multihost", action="store_true",
                    help="also gate the multihost section (nightly runs "
                         "it; quick local runs may not)")
    ap.add_argument("--require-repair", action="store_true",
                    help="also gate the plan-repair section (produced by "
                         "--only repair; nightly runs it)")
    ap.add_argument("--require-sampling", action="store_true",
                    help="also gate the neighbor-sampling section "
                         "(produced by --only sample; nightly runs it)")
    ap.add_argument("--parallel", choices=["auto", "on", "off"],
                    default="auto",
                    help="enforce the parallel-hardware gates (occupancy "
                         ">= 0.75, speedup >= 1.3); auto = cpu_count >= 4")
    args = ap.parse_args(argv)

    with open(args.results) as f:
        s = json.load(f)
    parallel = (args.parallel == "on"
                or (args.parallel == "auto"
                    and (os.cpu_count() or 1) >= 4))

    g = Gate()
    check_serving(g, s, parallel=parallel)
    if args.require_multihost:
        check_multihost(g, s)
    elif "multihost" in s:
        check_multihost(g, s)
    else:
        g.info("multihost section absent, skipped "
               "(pass --require-multihost to make that a failure)")
    if args.require_repair or "repair" in s:
        check_repair(g, s)
    else:
        g.info("repair section absent, skipped "
               "(pass --require-repair to make that a failure)")
    if args.require_sampling or "sampling" in s:
        check_sampling(g, s)
    else:
        g.info("sampling section absent, skipped "
               "(pass --require-sampling to make that a failure)")
    check_regression(g, s, args.baseline)

    if g.failures:
        print(f"\n{len(g.failures)} gate(s) failed:", file=sys.stderr)
        for msg in g.failures:
            print(f"  - {msg}", file=sys.stderr)
        return 1
    print("\nall gates passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
