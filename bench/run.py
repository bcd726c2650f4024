#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are named in ``BENCHMARK.json``
at the checkout root. The run builds its inputs from ``--seed``, warms up,
measures for ``--seconds``, checks the answers against the plain reference,
prints each compared number beside its limit on standard error, and ends
standard output with one JSON line. Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import NoChipError, run_cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChipError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']:.6e} (limit {c['limit']:.6e})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
