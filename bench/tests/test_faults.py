"""A whole run with the timed path broken underneath must come out as not
correct, once for each fault the cells can have (a one-chip cell has no
exchange between chips to leave out). The look for a chip is skipped (the
run is on the CPU, each cell at its configuration's tiny size); everything
else is the run as the benchmark makes it."""
import pytest

import repro.serve.graph_engine as graph_engine
from bench.harness import load_spec, metrics_of
from bench.tests.tiny import run_tiny

SPEC = load_spec()
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
real_spmm_batched = graph_engine.spmm_batched


def state_unchanged(*args, **kw):
    """The aggregation hands back its input features unchanged."""
    outs, decision = real_spmm_batched(*args, **kw)
    xs, n_rows = args[1], args[2]
    return [x[:n, :o.shape[1]] for x, n, o in zip(xs, n_rows, outs)], decision


def half_batch(*args, **kw):
    """Half of each answer's rows left out, the rest scaled to keep the
    mean: the second half reads zero, the first half twice its value."""
    outs, decision = real_spmm_batched(*args, **kw)
    fixed = []
    for o in outs:
        h = o.shape[0] // 2
        fixed.append(o.at[:h].multiply(2.0).at[h:].set(0.0))
    return fixed, decision


def one_answer_altered(*args, **kw):
    """One value of one answer altered where it is produced."""
    outs, decision = real_spmm_batched(*args, **kw)
    return [outs[0].at[0, 0].add(1.0)] + list(outs[1:]), decision


FAULTS = [state_unchanged, half_batch, one_answer_altered]


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(monkeypatch, workload, fault):
    monkeypatch.setattr(graph_engine, "spmm_batched", fault)
    result = run_tiny(workload, seed=2**31 + 77)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    result = run_tiny(workload, seed=2**31 + 78)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    # The CPU's trace has no device ops: a device-trace metric reads nothing.
    assert set(result["metrics"]) == {
        m["name"] for m in metrics_of(SPEC, workload, "end_to_end")
        if m["source"] != "device_trace"}
