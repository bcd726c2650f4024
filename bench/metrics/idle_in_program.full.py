"""Share of the traced window in which the device sat idle while the program
did host work: the device's idle intervals (as ``device_idle.full``)
under the program's ``sched.hold`` and ``gcn.dispatch.{prepare, merge,
upload, launch, answer}`` spans, averaged over the devices the cell uses.
The whole idle share, split by innermost program span (``wait`` is idle
while the host waits on the device), goes to standard error."""
import sys

from bench.spans import HOST_WORK, idle_split

UNIT = "%"
MOVES = "forward_device_ms"


def read(run):
    if run.trace is None:
        return None
    split = idle_split(run.trace)
    if split is None:
        print("[bench] idle_in_program: no program span in the window",
              file=sys.stderr)
        return None
    window_s = run.trace.window_s
    for name, seconds in sorted(split.items(), key=lambda kv: -kv[1]):
        print(f"[bench] device idle under {name}: "
              f"{100.0 * seconds / window_s:.3f}%", file=sys.stderr)
    print(f"[bench] device idle in all: "
          f"{100.0 * sum(split.values()) / window_s:.3f}%", file=sys.stderr)
    return 100.0 * sum(split.get(n, 0.0) for n in HOST_WORK) / window_s
