"""Backend compiles inside the measured window, counted by a
``jax.monitoring`` listener on ``/jax/core/compile/backend_compile_duration``
(persistent-cache loads fire it too). A warmed cell reads 0."""
UNIT = "compiles"
MOVES = "forward_device_ms"


def read(run):
    return float(run.compiles)
