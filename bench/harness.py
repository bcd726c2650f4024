"""One run of one cell: set-up, measured window, trace, metrics, check.

The steps, in order:

1. Find the cell in ``BENCHMARK.json`` and its configuration and traffic
   files; refuse a platform that is not a TPU or holds too few chips.
2. Set-up: JAX's persistent compilation cache in the checkout, writing
   every program the set-up compiles (``min_compile_time`` 0); the loop of
   the traffic's kind builds the system (graph kind, model, engine) and
   warms every shape its traffic uses.
3. Cache writes off (``min_compile_time`` 1e9): a program compiled inside
   the window is never written, so no later run can find it there.
4. The measured window, traced with ``--trace 1``, and also with
   ``--trace 0`` where an end-to-end metric of the cell comes from the
   device trace. Backend compiles inside it are counted by a
   ``jax.monitoring`` listener.
5. The device's peak memory, then the program's state is freed, and the
   plain reference judges the kept answers against the cell's limits.

The result is one JSON line: with ``--trace 0`` the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics. An end-to-end metric is
the loop's own where its ``end_to_end()`` reports it; every other metric is
read by its own file under ``metrics/``. Every part is found by name
(``loader.py``).
"""
from __future__ import annotations

import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Optional, Sequence

import jax

from bench import check, loader, system
from bench.work import load_peaks

ROOT = Path(__file__).resolve().parents[1]
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChipError(RuntimeError):
    """The platform is not a TPU, or holds fewer chips than the cell asks."""


def load_spec(path: Path = ROOT / "BENCHMARK.json") -> Dict:
    return json.loads(path.read_text())


def cell_of(spec: Dict, workload: str) -> Dict:
    for w in spec["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                   f"(known: {[w['name'] for w in spec['workloads']]})")


def metrics_of(spec: Dict, workload: str, section: str) -> Sequence[Dict]:
    """The metrics of ``section`` that this cell reports: those that list
    it, and those without a list whose ``moves`` metric it reports."""
    e2e = [m["name"] for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    out = []
    for m in spec[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def check_devices(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChipError(f"no TPU: JAX found {devices[0].platform}; the "
                          f"benchmark measures only on the chip")
    if len(devices) < chips:
        raise NoChipError(f"the cell needs {chips} chips, JAX found "
                          f"{len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Backend compiles (and persistent-cache loads) while ``on``."""

    def __init__(self):
        self.on = False
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if self.on and event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


class Run:
    """What a metric reader may read of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def counter(self, key: str) -> float:
        """Change of an engine counter across the window."""
        return float(self.stats1[key]) - float(self.stats0[key])


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, spec: Optional[Dict] = None,
             devices: Optional[Sequence] = None,
             config: Optional[Dict] = None, mix: Optional[Dict] = None
             ) -> Dict:
    """One run; returns the result line as a dict (``checks`` last).

    ``devices`` skips the look for a chip, and ``config`` and ``mix`` stand
    in for the cell's files: the tests drive a run on the CPU at a small
    size."""
    spec = spec or load_spec()
    cell = cell_of(spec, workload)
    config = config or loader.load_json("configs", cell["config"])
    mix = mix or loader.load_json("traffic", cell["traffic"])
    if devices is None:
        devices = check_devices(int(cell["chips"]))
    kind = devices[0].device_kind
    peaks = load_peaks(kind) if devices[0].platform == "tpu" else None

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    traced = trace or any(m["source"] == "device_trace" for m
                          in metrics_of(spec, workload, "end_to_end"))
    loop = system.make_loop(config, mix, seed, devices)
    try:
        loop.setup()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        stats0 = loop.engine.stats()
        setup_s = time.perf_counter() - t_start
        counter.on = True
        if traced:
            jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            loop.window(seconds)
        if traced:
            jax.profiler.stop_trace()
        counter.on = False
        stats1 = loop.engine.stats()
        memory_peak = _memory_peak(devices)
        e2e = dict(loop.end_to_end(), setup_s=setup_s)
        attempted, failed = loop.attempted_failed()
        run = Run(stats0=stats0, stats1=stats1, compiles=counter.count,
                  compile_s=counter.seconds, window_s=loop.window_s,
                  units=loop.units(), work=loop.work(), peaks=peaks,
                  chips=len(devices), end_to_end=e2e, trace=None)
        print(f"[bench] {workload}: window {loop.window_s:.3f} s, "
              f"{counter.count} backend compiles in it "
              f"({counter.seconds:.3f} s)", file=sys.stderr)
        summary = None
        if traced:
            from bench.trace import NoMatchingEvents, TraceSummary, load_xplane
            try:
                summary = TraceSummary(load_xplane(trace_dir),
                                       devices=[d.id for d in devices])
            except NoMatchingEvents:
                if trace:
                    raise
            shutil.rmtree(trace_dir, ignore_errors=True)
            run.trace = summary
        answers = loop.answers()
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
        loop.close()
    numbers = check.compare(loop.reference_pairs(answers, "highest"))
    correct, checks = check.judge(numbers, check.load_limits(workload))
    if failed:
        correct = False

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(spec, workload, section):
        value = (e2e[m["name"]] if not trace and m["name"] in e2e
                 else loader.load("metrics", m["name"]).read(run))
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=summary.busy_s(), window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_modules(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["checks"] = checks
    return result
