"""Finds a cell's parts by name, one file each, under ``BENCH_DIR`` (a
test may point it at a directory of its own):

* ``configs/<name>.json``, ``traffic/<name>.json``, ``limits/<cell>.json``:
  data;
* ``models/<kind>.py``, named by ``config["model"]["kind"]``: ``prepare(csr)
  -> csr`` (the model's normalisation), ``make_inputs(config, n, sets,
  seed) -> (xs, params)``, ``forward(engine, graph_id, params, x)`` (one
  pass through the engine), ``work(graph, config) -> {name: Work}`` (one
  pass's compulsory work), ``reference_pairs(graph, params, x, precision)
  -> (ref, terms)`` and ``tiny(config) -> config`` (a CPU test's size);
* ``data/<kind>.py``, a graph kind named by ``config["graph"]["kind"]``:
  ``build(graph_cfg, seed) -> [csr, ...]``;
* ``loops/<kind>.py``, a traffic kind named by ``traffic["kind"]``: ``Loop(
  config, traffic, seed, devices, model)`` with ``setup()``,
  ``window(seconds)``, ``window_s``, ``engine``, ``end_to_end()``,
  ``attempted_failed()``, ``work()``, ``units()``, ``answers()``,
  ``reference_pairs(answers, precision)`` and ``close()``, as
  ``harness.run_cell`` calls them;
* ``metrics/<name>.py``: the reader of one metric, ``read(run)``: of each
  per-layer metric, and of each end-to-end metric that the loop's
  ``end_to_end()`` does not report.

A new part is a new file: no code here or elsewhere lists them.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Dict

BENCH_DIR = Path(__file__).resolve().parent


def _path(kind: str, name: str, suffix: str) -> Path:
    path = BENCH_DIR / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return path


def load(kind: str, name: str) -> ModuleType:
    """``<BENCH_DIR>/<kind>/<name>.py``, executed as a fresh module."""
    path = _path(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(kind: str, name: str) -> Dict:
    """``<BENCH_DIR>/<kind>/<name>.json``."""
    return json.loads(_path(kind, name, ".json").read_text())
