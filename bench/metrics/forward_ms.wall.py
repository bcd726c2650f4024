"""Wall time of one forward pass on the host clock: the measured window over
the passes completed in it, as the loop reports it (``forward_ms``). Each
process merges slabs at a speed of its own, so runs of one cell spread too
widely for a bound; ``forward_device_ms`` carries the bound."""
UNIT = "ms"
MOVES = "forward_device_ms"


def read(run):
    return run.end_to_end.get("forward_ms")
