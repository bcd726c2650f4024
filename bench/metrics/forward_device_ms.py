"""Device time of one forward pass: the union of the device's op intervals
inside the measured window, averaged over the chips used, over the passes
completed in it. The host's part of a pass, the slab merge with it, does not
enter it: ``forward_ms.wall`` reads the pass on the host clock."""
UNIT = "ms"


def read(run):
    if run.trace is None or not run.units:
        return None
    return 1e3 * run.trace.busy_s() / run.units
