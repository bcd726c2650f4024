"""Production mesh construction (DESIGN.md §6).

A function, not a module-level constant, so importing never touches jax
device state. Target: TPU v5e, 256 chips/pod; multi-pod adds a leading "pod"
axis for hierarchical (ICI-within-pod / DCN-across-pod) collectives.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the models shard activations with
    ``with_sharding_constraint``, which Explicit axes (the make_mesh
    default since JAX 0.7) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def as_auto_mesh(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis Auto (a mesh from a
    plain ``jax.make_mesh`` call comes back Explicit)."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"model axis size must be >= 1, got {model}")
    if n % model != 0:
        raise ValueError(
            f"cannot build a ({n // model if model else 0}, {model}) host "
            f"mesh: {n} available device(s) not divisible by model={model}")
    return _auto_mesh((n // model, model), ("data", "model"))


def multihost_graph_mesh() -> Mesh:
    """Global 1-D serving mesh spanning EVERY process's devices.

    The cross-host analogue of :func:`graph_mesh`: one flat "dev" axis over
    ``jax.devices()`` — which, after ``jax.distributed.initialize``, is the
    union of all processes' local devices in process-major order. Any
    computation over this mesh is SPMD-collective: every process must enter
    it with the same program (the ``MultihostGraphEngine.serve_global``
    contract). On a single process it degenerates to ``graph_mesh()``.
    """
    devices = jax.devices()
    if not devices:
        raise ValueError("multihost_graph_mesh found no devices")
    return Mesh(np.asarray(devices), ("dev",))


def graph_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh for fleet graph serving: ``n_devices`` devices on axis "dev".

    Unlike the train meshes there is no data/model split — graph serving
    parallelism is the paper's column-dimension (feature) parallelism and
    block-level workload balancing lifted to device granularity, both of
    which want a flat device axis. Defaults to every visible device; a
    smaller ``n_devices`` takes a prefix (so a fleet engine can leave
    devices for other tenants).
    """
    avail = jax.devices()
    n = len(avail) if n_devices is None else int(n_devices)
    if n < 1:
        raise ValueError(f"graph_mesh needs >= 1 device, got n_devices={n}")
    if n > len(avail):
        raise ValueError(
            f"graph_mesh(n_devices={n}) exceeds the {len(avail)} visible "
            f"device(s)")
    return Mesh(np.asarray(avail[:n]), ("dev",))
