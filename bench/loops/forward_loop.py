"""Traffic kind ``forward_loop``: one client runs back-to-back full forward
passes of the configuration's model over its one graph. ``feature_sets``
input matrices are made from the seed and used in turn.

The loop warms every shape its traffic uses before the window opens (two
passes: one compiles, one runs warm), and keeps the answers the comparison
needs.
"""
from __future__ import annotations

import time
from typing import Dict, Sequence

import jax
import numpy as np

from bench import system
from bench.data import rng_for
from bench.work import Work


class _Counted:
    """The engine as the model sees it, counting the requests sent."""

    def __init__(self, engine):
        self.engine, self.sent = engine, 0

    def submit(self, *args, **kw):
        self.sent += 1
        return self.engine.submit(*args, **kw)


class Loop:
    """Closed loop of full forward passes over one large graph."""

    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 devices: Sequence, model):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.model = devices, model
        self.k_sets = int(traffic["feature_sets"])
        self.gid = config["name"]
        self.kept: Dict[int, jax.Array] = {}
        self.passes = self.requests = 0
        self.window_s = 0.0

    # -------------------------------------------------------------- set-up
    def _make_inputs(self):
        return self.model.make_inputs(self.config, self.n, self.k_sets,
                                      self.seed)

    def setup(self) -> None:
        (self.graph,) = system.build_graphs(self.config["graph"], self.seed,
                                            self.model.prepare)
        self.n = len(self.graph[0]) - 1
        self.engine = system.make_engine(self.config["engine"])
        self.client = _Counted(self.engine)
        system.register(self.engine, self.gid, self.graph)
        xs, self.params = self._make_inputs()
        self.xs = [xs[i] for i in range(self.k_sets)]
        for _ in range(2):                   # compile, then one warm pass
            self._forward(self.xs[0])
        self.check_pass = int(rng_for(self.seed, 4).integers(self.k_sets))

    def _forward(self, x):
        return self.model.forward(self.client, self.gid, self.params, x)

    # -------------------------------------------------------------- window
    def window(self, seconds: float) -> None:
        sent0 = self.client.sent
        t0 = time.perf_counter()
        while True:
            p = self.passes
            with jax.profiler.TraceAnnotation("bench.forward"):
                logits = self._forward(self.xs[p % self.k_sets])
            if p < self.k_sets:
                self.kept[p] = logits
            self.passes += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        self.requests += self.client.sent - sent0

    # ------------------------------------------------------------- results
    def end_to_end(self) -> Dict[str, float]:
        return {"forward_ms": 1e3 * self.window_s / self.passes}

    def attempted_failed(self):
        return self.requests, 0

    def work(self) -> Dict[str, Work]:
        return {name: w.scaled(self.passes) for name, w
                in self.model.work(self.graph, self.config).items()}

    def units(self) -> int:
        return self.passes

    def answers(self):
        p = min(self.check_pass, self.passes - 1)
        return p, np.asarray(self.kept[p])

    def close(self) -> None:
        if hasattr(self, "engine"):
            self.engine.close()
        self.kept.clear()
        self.xs = self.params = None

    def reference_pairs(self, answers, precision: str):
        """(got, ref, terms) for the kept pass: the model's reference runs
        on that pass's inputs, rebuilt from the seed."""
        p, got = answers
        xs, params = self._make_inputs()
        x = xs[p % self.k_sets]
        del xs
        ref, terms = self.model.reference_pairs(self.graph, params, x,
                                                precision)
        return [(got, ref, terms)]
