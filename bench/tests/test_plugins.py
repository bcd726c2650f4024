"""A cell whose model, graph kind and traffic kind are new files, written
into a directory of the test's own, runs through ``run_cell`` without a
change to any file of the benchmark: a one-aggregation model with a plain
numpy reference, a ring graph, and a loop of single requests. A model that
alters one value of its answer comes out not correct."""
import hashlib
import json
import time
from pathlib import Path

import jax
import pytest

from bench import loader
from bench.harness import run_cell

BENCH_DIR = loader.BENCH_DIR

GRAPH_KIND = '''
import numpy as np

from bench.data import csr_from_edges, rng_for


def build(graph_cfg, seed):
    n = graph_cfg["nodes"]
    hop = int(rng_for(seed, 0).integers(2, n - 1))
    src = np.concatenate([np.arange(n), np.arange(n)])
    dst = np.concatenate([(np.arange(n) + 1) % n, (np.arange(n) + hop) % n])
    return [csr_from_edges(src, dst, n)]
'''

MODEL_KIND = '''
import jax
import jax.numpy as jnp
import numpy as np

from bench.work import spmm_work

ALTER = {alter}


def prepare(g):
    rowptr, colidx, values = g
    deg = np.diff(rowptr)
    return rowptr, colidx, (values / np.repeat(deg, deg)).astype(np.float32)


def make_inputs(config, n, sets, seed):
    rng = np.random.default_rng([seed, 2])
    x = rng.standard_normal((sets, n, config["model"]["width"]))
    return jnp.asarray(x, jnp.float32), None


def forward(engine, graph_id, params, x):
    y = engine.submit(graph_id, x).result()
    return jax.block_until_ready(y.at[0, 0].add(1.0) if ALTER else y)


def work(graph, config):
    n = len(graph[0]) - 1
    return {{"spmm": spmm_work(len(graph[1]), n, n,
                              config["model"]["width"])}}


def reference_pairs(graph, params, x, precision):
    rowptr, colidx, values = graph
    x = np.asarray(x, np.float64)
    rows = np.repeat(np.arange(len(rowptr) - 1), np.diff(rowptr))
    ref = np.zeros_like(x)
    np.add.at(ref, rows, values[:, None].astype(np.float64) * x[colidx])
    return ref, np.diff(rowptr)


def tiny(config):
    return config
'''

LOOP_KIND = '''
import time

import numpy as np

from bench import system


class Loop:
    def __init__(self, config, traffic, seed, devices, model):
        self.config, self.seed, self.model = config, seed, model
        self.calls, self.window_s = 0, 0.0

    def setup(self):
        (self.graph,) = system.build_graphs(self.config["graph"], self.seed,
                                            self.model.prepare)
        self.n = len(self.graph[0]) - 1
        self.engine = system.make_engine(self.config["engine"])
        system.register(self.engine, "g", self.graph)
        self.xs, self.params = self.model.make_inputs(self.config, self.n, 1,
                                                      self.seed)
        self.model.forward(self.engine, "g", self.params, self.xs[0])

    def window(self, seconds):
        t0 = time.perf_counter()
        while True:
            y = self.model.forward(self.engine, "g", self.params, self.xs[0])
            if self.calls == 0:
                self.kept = np.asarray(y)
            self.calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0

    def end_to_end(self):
        return {"call_ms": 1e3 * self.window_s / self.calls}

    def attempted_failed(self):
        return self.calls, 0

    def work(self):
        return {k: w.scaled(self.calls)
                for k, w in self.model.work(self.graph, self.config).items()}

    def units(self):
        return self.calls

    def answers(self):
        return self.kept

    def close(self):
        self.engine.close()

    def reference_pairs(self, answers, precision):
        xs, params = self.model.make_inputs(self.config, self.n, 1, self.seed)
        ref, terms = self.model.reference_pairs(self.graph, params, xs[0],
                                                precision)
        return [(answers, ref, terms)]
'''

SPEC = {
    "workloads": [{"name": "ring-calls", "config": "ring-mean",
                   "traffic": "calls", "chips": 1, "why": "a test cell"}],
    "end_to_end": [
        {"name": "call_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": [],
}


def bench_files_digest() -> str:
    h = hashlib.sha256()
    for path in sorted(BENCH_DIR.rglob("*")):
        if path.is_file() and "__pycache__" not in path.parts:
            h.update(str(path).encode() + path.read_bytes())
    return h.hexdigest()


def write_parts(root: Path, alter: bool) -> None:
    files = {
        "data/ring.py": GRAPH_KIND,
        "models/mean1.py": MODEL_KIND.format(alter=alter),
        "loops/single_calls.py": LOOP_KIND,
        "configs/ring-mean.json": json.dumps({
            "name": "ring-mean", "graph": {"kind": "ring", "nodes": 300},
            "model": {"kind": "mean1", "width": 8},
            "engine": {"backend": "auto"}}),
        "traffic/calls.json": json.dumps({"kind": "single_calls"}),
        "limits/ring-calls.json": json.dumps(
            {"limits": {"row_err": {"limit": 1e-6}}}),
    }
    for name, text in files.items():
        (root / name).parent.mkdir(parents=True, exist_ok=True)
        (root / name).write_text(text)


@pytest.mark.parametrize("alter", [False, True])
def test_a_cell_of_new_files_runs(tmp_path, monkeypatch, alter):
    before = bench_files_digest()
    write_parts(tmp_path, alter)
    monkeypatch.setattr(loader, "BENCH_DIR", tmp_path)
    result = run_cell("ring-calls", 2**31 + 21, 0.3, False,
                      t_start=time.perf_counter(), spec=SPEC,
                      devices=jax.devices()[:1])
    assert result["correct"] is (not alter)
    assert set(result["metrics"]) == {"call_ms", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert bench_files_digest() == before
