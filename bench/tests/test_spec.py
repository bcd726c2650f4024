"""BENCHMARK.json against the rules the harness relies on, and the refusal
of a platform without a TPU."""
import json
import re
import subprocess
import sys

import pytest

from bench import harness, loader
from bench.tests.conftest import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_and_units():
    names = [c["name"] for c in SPEC["configs"]] + \
        [w["name"] for w in SPEC["workloads"]] + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]] + \
        [w["traffic"] for w in SPEC["workloads"]]
    assert all(NAME.match(n) for n in names), names
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in SPEC["workloads"]:
        e2e = [m["name"] for m in harness.metrics_of(SPEC, w["name"],
                                                     "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_of(SPEC, w["name"], "per_layer")


def test_per_layer_moves_a_metric_its_cells_report():
    for m in SPEC["per_layer"]:
        for w in m["workloads"]:
            e2e = [e["name"] for e in harness.metrics_of(SPEC, w,
                                                         "end_to_end")]
            assert m["moves"] in e2e, (m["name"], w)


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_reader_file_agrees_with_the_spec(metric):
    reader = loader.load("metrics", metric["name"])
    assert reader.UNIT == metric["unit"] and reader.MOVES == metric["moves"]


def test_device_trace_end_to_end_metrics_have_a_reader():
    """An end-to-end metric that the loop cannot clock, one from the device
    trace, is read by its own file."""
    for m in SPEC["end_to_end"]:
        if m["source"] == "device_trace":
            assert loader.load("metrics", m["name"]).UNIT == m["unit"]


MODEL_API = ("prepare", "make_inputs", "forward", "work", "reference_pairs",
             "tiny")


def test_cells_find_their_files():
    """Each cell's configuration, traffic and limits, and the model kind,
    graph kind and traffic kind they name, each resolve to a file."""
    for w in SPEC["workloads"]:
        config = loader.load_json("configs", w["config"])
        assert config["name"] == w["config"]
        model = loader.load("models", config["model"]["kind"])
        assert all(callable(getattr(model, f, None)) for f in MODEL_API)
        assert callable(loader.load("data", config["graph"]["kind"]).build)
        kind = loader.load_json("traffic", w["traffic"])["kind"]
        assert isinstance(loader.load("loops", kind).Loop, type)
        assert loader.load_json("limits", w["name"])["limits"]
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).exists()


def test_a_missing_part_names_its_file():
    with pytest.raises(FileNotFoundError, match="no models file for 'gat'"):
        loader.load("models", "gat")


def test_no_tpu_exits_nonzero_and_prints_no_result():
    w = SPEC["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", w,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
