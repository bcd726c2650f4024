"""Compulsory work of the benchmark's operations, counted from shapes.

The counts are what the mathematics needs, whatever implements it: a later
change that fuses, pads or re-tiles does not change them. So a share of a
roofline built on them stays under 100% by construction.

SpMM ``Y = A' X`` with ``A'`` in CSR (``n_rows x n_cols``, ``nnz``
nonzeros) and ``F`` feature columns (the request's own width, unpadded):
  bytes = A' in CSR (nnz * (4 + 4) + (n_rows + 1) * 4)
          + X read once (n_cols * F * 4) + Y written once (n_rows * F * 4)
  flops = 2 * nnz * F
Dense ``H W`` (``n x f_in`` by ``f_in x f_out``):
  flops = 2 * n * f_in * f_out
  bytes = (n * f_in + f_in * f_out + n * f_out) * 4
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict

F32 = 4
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scaled(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def spmm_work(nnz: int, n_rows: int, n_cols: int, f: int) -> Work:
    return Work(flops=2.0 * nnz * f,
                bytes=float(nnz * (F32 + 4) + (n_rows + 1) * 4
                            + n_cols * f * F32 + n_rows * f * F32))


def dense_work(n: int, f_in: int, f_out: int) -> Work:
    return Work(flops=2.0 * n * f_in * f_out,
                bytes=float((n * f_in + f_in * f_out + n * f_out) * F32))


def load_peaks(device_kind: str) -> Dict[str, float]:
    """Peak FLOP/s and HBM bytes/s of one chip of this kind; a kind that
    ``peaks.json`` does not list is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def compulsory_s(work: Work, peaks: Dict[str, float]) -> float:
    """Least time one chip could take: the larger of the compute bound and
    the memory bound."""
    return max(work.flops / peaks["flops_per_s"],
               work.bytes / peaks["hbm_bytes_per_s"])
