"""Share of the traced window in which no op ran on the device: one minus
the union of the device's op intervals over the window, averaged over the
devices the cell uses."""
UNIT = "%"
MOVES = "forward_device_ms"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share()
