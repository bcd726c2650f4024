#!/usr/bin/env python3
"""
Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload arxiv-fullgraph --seconds 3 \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12

For each seed: one set-up of the cell, a short window at the cell's own
load, then the comparison numbers of the program's kept answers against the
float32 reference (the lower reading), and of the control, the reference
computed with three-pass bfloat16 products (``precision="high"``) in the
program's place, against the same reference (the upper reading). One JSON
line per seed, and the largest program reading and smallest control reading
of each number at the end.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import check, harness, system, traffic
    from repro.launch.compile_cache import enable_compile_cache
    cell = harness.cell_of(harness.load_spec(), args.workload)
    try:
        devices = harness.check_devices(int(cell["chips"]))
    except harness.NoChipError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = system.load_json("configs", cell["config"])
    mix = system.load_json("traffic", cell["traffic"])
    worst = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        loop = traffic.make_driver(config, mix, seed, devices)
        try:
            loop.setup()
            loop.window(args.seconds)
            answers = loop.answers()
        finally:
            loop.close()
        exact = loop.reference_pairs(answers, "highest")
        lower = loop.reference_pairs(answers, "high")
        program = check.compare(exact)
        control = check.compare((lo, ex, terms) for (_, lo, terms), (_, ex, _)
                                in zip(lower, exact))
        print(json.dumps({"seed": seed, "program": program,
                          "control": control,
                          "seconds": time.perf_counter() - t0}), flush=True)
        for k in check.NUMBERS:
            lo, up = worst.get(k, (0.0, float("inf")))
            worst[k] = (max(lo, program[k]), min(up, control[k]))
    print(json.dumps({"lower_reading": {k: v[0] for k, v in worst.items()},
                      "upper_reading": {k: v[1] for k, v in worst.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
