"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

Every Pallas entry point asks :func:`pallas_interpret` at call time instead
of taking an ``interpret`` option, so a program on a TPU host can never fall
back to the interpreter by default, and a platform with no Pallas TPU
lowering fails loudly instead of interpreting.
"""
from __future__ import annotations

import jax

__all__ = ["pallas_interpret"]


def pallas_interpret() -> bool:
    """True on the CPU (interpret mode), False on a TPU (Mosaic compile).

    Raises ``RuntimeError`` on any other platform: these kernels are
    written for the TPU, and interpreting them on an accelerator would hide
    the device behind a slow emulation.
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on platform {platform!r}: "
        f"use a TPU, or the CPU for interpret-mode tests")
