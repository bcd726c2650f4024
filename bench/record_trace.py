#!/usr/bin/env python3
"""Record one traced forward pass of a cell as a test fixture.

    python3 bench/record_trace.py --workload <name> --seed <n> --out <file.json.gz>

Builds and warms the cell's system as a run does, traces one pass inside a
``bench.window`` span, and writes what ``trace.load_events`` reads: the
device ops and modules, the harness's ``bench.*`` spans and the program's
spans (``spans.PROGRAM_SPANS``) that overlap the window. The Python
tracer's frames are left out. It needs the chip the cell names.
"""
import argparse
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, loader, system
    from bench.spans import PROGRAM_SPANS
    from bench.trace import (HOST_PLANE, TraceSummary, load_xplane,
                             save_events)
    from repro.launch.compile_cache import enable_compile_cache

    cell = harness.cell_of(harness.load_spec(), args.workload)
    devices = harness.check_devices(int(cell["chips"]))
    enable_compile_cache()
    loop = system.make_loop(loader.load_json("configs", cell["config"]),
                            loader.load_json("traffic", cell["traffic"]),
                            args.seed, devices)
    trace_dir = tempfile.mkdtemp(prefix="bench-record-")
    try:
        loop.setup()
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            loop.window(0.0)                  # one pass
        jax.profiler.stop_trace()
    finally:
        loop.close()
    events = load_xplane(trace_dir)
    summary = TraceSummary(events, devices=[d.id for d in devices])
    kept = [e for e in events
            if e.end_ns > summary.t0 and e.start_ns < summary.t1
            and (e.plane != HOST_PLANE or e.name.startswith("bench.")
                 or e.name in PROGRAM_SPANS)]
    save_events(kept, args.out)
    print(f"{len(kept)} events, window {summary.window_s:.6f} s, "
          f"device idle {100 * summary.idle_share():.3f}% -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
