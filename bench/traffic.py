"""The one traffic generator: drives a cell's system from a traffic file.

A traffic mix (``traffic/<mix>.json``) is data: its ``kind`` picks a loop,
and the rest are the loop's parameters. The one kind so far:

* ``forward_loop``: one client runs back-to-back full forward passes of the
  configuration's GCN over its one graph. Each aggregation is one
  ``engine.submit(...).result()``; the dense ``H W`` and the ReLU run on the
  device between aggregations. ``feature_sets`` input matrices are made
  from the seed and used in turn.

A loop warms every shape its traffic uses before the window opens, and
keeps the answers the comparison needs.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import system
from bench.data import graphs as data
from bench.references import gcn as reference
from bench.work import Work, dense_work, spmm_work


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


class ForwardLoop:
    """Closed loop of full GCN forward passes over one large graph."""

    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 devices: Sequence):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.widths = list(config["model"]["widths"])
        self.k_sets = int(traffic["feature_sets"])
        self.gid = config["name"]
        self.kept: Dict[int, jax.Array] = {}
        self.passes = 0
        self.window_s = 0.0

    # -------------------------------------------------------------- set-up
    def _make_inputs(self):
        n, widths, k_sets = self.n, self.widths, self.k_sets

        @jax.jit
        def make(key):
            kx, *kw = jax.random.split(key, len(widths))
            xs = jax.random.normal(kx, (k_sets, n, widths[0]), jnp.float32)
            ws = [jax.random.normal(k, (fi, fo), jnp.float32)
                  * jnp.sqrt(2.0 / (fi + fo))
                  for k, fi, fo in zip(kw, widths[:-1], widths[1:])]
            return xs, ws

        return make(jax.random.key(system.jax_seed(self.seed, 2)))

    def setup(self) -> None:
        (self.graph,) = system.build_graphs(self.config["graph"], self.seed)
        self.n = len(self.graph[0]) - 1
        self.engine = system.make_engine(self.config["engine"])
        system.register(self.engine, self.gid, self.graph)
        xs, self.ws = self._make_inputs()
        self.xs = [xs[i] for i in range(self.k_sets)]
        self._dense = jax.jit(partial(jnp.dot,
                                      precision=jax.lax.Precision.HIGHEST))
        self._relu = jax.jit(jax.nn.relu)
        for _ in range(2):                   # compile, then one warm pass
            self._forward(self.xs[0])
        self.check_pass = int(data.rng_for(self.seed, 4).integers(
            self.k_sets))

    def _forward(self, x):
        h = x
        for i, w in enumerate(self.ws):
            with annotate("bench.dense"):
                hw = self._dense(h, w)
            with annotate("bench.aggregate"):
                h = self.engine.submit(self.gid, hw).result()
            if i < len(self.ws) - 1:
                h = self._relu(h)
        return jax.block_until_ready(h)

    # -------------------------------------------------------------- window
    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while True:
            p = self.passes
            with annotate("bench.forward"):
                logits = self._forward(self.xs[p % self.k_sets])
            if p < self.k_sets:
                self.kept[p] = logits
            self.passes += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0

    # ------------------------------------------------------------- results
    def end_to_end(self) -> Dict[str, float]:
        return {"forward_ms": 1e3 * self.window_s / self.passes}

    def attempted_failed(self):
        return self.passes * len(self.ws), 0

    def work(self) -> Dict[str, Work]:
        nnz = len(self.graph[1])
        spmm = dense = Work()
        for fi, fo in zip(self.widths[:-1], self.widths[1:]):
            dense = dense + dense_work(self.n, fi, fo)
            spmm = spmm + spmm_work(nnz, self.n, self.n, fo)
        return {"spmm": spmm.scaled(self.passes),
                "dense": dense.scaled(self.passes)}

    def units(self) -> int:
        return self.passes

    def answers(self):
        p = min(self.check_pass, self.passes - 1)
        return p, np.asarray(self.kept[p])

    def close(self) -> None:
        if hasattr(self, "engine"):
            self.engine.close()
        self.kept.clear()
        self.xs = self.ws = None

    def reference_pairs(self, answers, precision: str):
        """(got, ref, terms) for the kept pass: the reference rebuilds that
        pass's inputs from the seed and runs the plain forward pass; terms
        is the number of terms each row of the last aggregation sums."""
        p, got = answers
        xs, ws = self._make_inputs()
        x = xs[p % self.k_sets]
        del xs
        ref = reference.gcn_forward(self.graph, x, ws, precision)
        return [(got, np.asarray(ref), np.diff(self.graph[0]))]


DRIVERS = {"forward_loop": ForwardLoop}


def make_driver(config: Dict, traffic: Dict, seed: int, devices: Sequence):
    return DRIVERS[traffic["kind"]](config, traffic, seed, devices)
