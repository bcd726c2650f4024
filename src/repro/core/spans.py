"""Named host spans of the served path, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``: under a running profiler
it lands on the host plane of the same trace as the device ops, from
whichever thread opened it; with no profiler it costs under a microsecond.
``span(name, acc)`` also adds the span's elapsed ``time.perf_counter()``
seconds to ``acc[name]``, so a layer's counter and its span agree by
construction. The counters live in the ``stats()`` of the layer that owns
them (scheduler, engine, plan cache).

The dispatch phases run in this order and partition the time the engine
counts in ``total_serve_s`` (``PREPARE`` also covers the feature concat
inside ``spmm_batched``; ``SCORES`` runs only in a dispatch that carries
attention requests); ``ANSWER`` follows it::

    gcn.dispatch
      prepare -> merge -> upload -> scores -> launch -> wait | answer
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

import jax

SCHED_HOLD = "sched.hold"          # scheduler holds queued items open
DISPATCH = "gcn.dispatch"          # one fused engine dispatch, end to end
PREPARE = "gcn.dispatch.prepare"   # cast, concat, bucket-pad the features
MERGE = "gcn.dispatch.merge"       # host merge of the plans' slabs
UPLOAD = "gcn.dispatch.upload"     # merged slabs host -> device
SCORES = "gcn.dispatch.scores"     # launch of per-call attention values
LAUNCH = "gcn.dispatch.launch"     # route + asynchronous kernel call
WAIT = "gcn.dispatch.wait"         # host blocks until the outputs are ready
ANSWER = "gcn.dispatch.answer"     # un-permute and slice the answers
PLAN_BUILD = "gcn.plan.build"      # one partition-plan build (not a load)

DISPATCH_PHASES = (PREPARE, MERGE, UPLOAD, SCORES, LAUNCH, WAIT, ANSWER)


@contextlib.contextmanager
def span(name: str, acc: Optional[Dict[str, float]] = None
         ) -> Iterator[None]:
    """Trace ``name`` over the block; add its seconds to ``acc[name]``."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        try:
            yield
        finally:
            if acc is not None:
                acc[name] = acc.get(name, 0.0) + time.perf_counter() - t0
