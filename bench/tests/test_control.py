"""The control: the plain reference put in the program's place, computed one
precision step below the configuration's (three-pass bfloat16 for float32 at
HIGHEST). In every cell, at its configuration's tiny size on the CPU, on
three seeds, the program comes out correct under the cell's limits and the
control does not. The readings the
limits were set from come from ``bench/calibrate.py`` on the chip, at the
cell's size."""
import jax
import pytest

from bench import check, system
from bench.harness import load_spec
from bench.tests.tiny import tiny_cell

WORKLOADS = [w["name"] for w in load_spec()["workloads"]]


def readings(workload: str, seed: int):
    config, mix = tiny_cell(workload)
    loop = system.make_loop(config, mix, seed, jax.devices()[:1])
    try:
        loop.setup()
        loop.window(0.3)
        answers = loop.answers()
    finally:
        loop.close()
    exact = loop.reference_pairs(answers, "highest")
    lower = loop.reference_pairs(answers, "high")
    program = check.compare(exact)
    control = check.compare((lo, ex, terms) for (_, lo, terms), (_, ex, _)
                            in zip(lower, exact))
    return program, control


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("seed", [5, 2**31 + 9, 12_345_678_901])
def test_control_fails_and_program_passes(workload, seed):
    limits = check.load_limits(workload)
    program, control = readings(workload, seed)
    assert check.judge(program, limits)[0] is True
    assert check.judge(control, limits)[0] is False
