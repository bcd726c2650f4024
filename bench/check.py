"""The comparison that decides ``correct``.

Every number is computed in float64 on the host from the program's answers
and the plain reference's, one ``(got, ref, terms)`` triple per answer,
where ``terms[r]`` is the number of terms row ``r``'s last sum adds up; each
number is the largest over the answers compared:

* ``rel_err``: ``||got - ref||_F / ||ref||_F``;
* ``max_err``: ``max|got - ref| / max|ref|``;
* ``med_err``: ``median|got - ref| / median|ref|``, the typical element's
  error;
* ``row_err``: the largest, over rows ``r``, of
  ``||got_r - ref_r|| / (max(||ref_r||, median_r ||ref_r||) * sqrt(terms_r))``.
  Round-off of a float32 sum of ``k`` terms grows as ``sqrt(k)``, so this is
  each row's error in units of what a sum of its length may carry in any
  order. A longer row does not swamp it, and one altered value shows.

A cell's limits file (``limits/<workload>.json``) says which numbers are
held to which limit, and the readings each limit was set from. A number
that is not finite, or an answer of the wrong shape, fails.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Iterable, Tuple

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parent / "limits"
NUMBERS = ("rel_err", "max_err", "med_err", "row_err")


def _numbers(got: np.ndarray, ref: np.ndarray, terms: np.ndarray
             ) -> Dict[str, float]:
    diff = got - ref
    ref_rows = np.linalg.norm(ref, axis=1)
    row_scale = np.maximum(ref_rows, np.median(ref_rows)) * np.sqrt(terms)
    return {
        "rel_err": float(np.linalg.norm(diff))
        / max(float(np.linalg.norm(ref)), 1e-300),
        "max_err": float(np.max(np.abs(diff), initial=0.0))
        / max(float(np.max(np.abs(ref), initial=0.0)), 1e-300),
        "med_err": float(np.median(np.abs(diff)))
        / max(float(np.median(np.abs(ref))), 1e-300),
        "row_err": float(np.max(np.linalg.norm(diff, axis=1)
                                / np.maximum(row_scale, 1e-300),
                                initial=0.0)),
    }


def compare(triples: Iterable[Tuple[np.ndarray, np.ndarray, np.ndarray]]
            ) -> Dict[str, float]:
    worst = dict.fromkeys(NUMBERS, 0.0)
    n = 0
    for got, ref, terms in triples:
        got = np.asarray(got, np.float64)
        ref = np.asarray(ref, np.float64)
        n += 1
        if got.shape != ref.shape or not np.all(np.isfinite(got)):
            return dict.fromkeys(NUMBERS, math.inf) | {"answers": n}
        for k, v in _numbers(got, ref, np.asarray(terms, np.float64)).items():
            worst[k] = max(worst[k], v)
    if n == 0:
        return dict.fromkeys(NUMBERS, math.inf) | {"answers": 0}
    return worst | {"answers": n}


def load_limits(workload: str) -> Dict[str, float]:
    path = LIMITS_DIR / f"{workload}.json"
    spec = json.loads(path.read_text())
    return {name: float(v["limit"]) for name, v in spec["limits"].items()}


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and each compared number beside its limit."""
    checks = {name: {"value": numbers[name], "limit": lim}
              for name, lim in limits.items()}
    ok = bool(limits) and all(math.isfinite(c["value"])
                              and c["value"] <= c["limit"]
                              for c in checks.values())
    return ok, checks
