"""The control: the plain reference put in the program's place, computed one
precision step below the configuration's (three-pass bfloat16 for float32 at
HIGHEST). At a small size on the CPU, on three seeds, the program comes out
correct under the cell's limits and the control does not. The readings the
limits were set from come from ``bench/calibrate.py`` on the chip, at the
cell's size."""
import jax
import pytest

from bench import check, system
from bench.harness import load_spec
from bench.tests.tiny import tiny_config
from bench.traffic import make_driver

WORKLOAD = load_spec()["workloads"][0]


def readings(seed: int):
    mix = system.load_json("traffic", WORKLOAD["traffic"])
    loop = make_driver(tiny_config(), mix, seed, jax.devices()[:1])
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


@pytest.mark.parametrize("seed", [5, 2**31 + 9, 12_345_678_901])
def test_control_fails_and_program_passes(seed):
    limits = check.load_limits(WORKLOAD["name"])
    program, control = readings(seed)
    assert check.judge(program, limits)[0] is True
    assert check.judge(control, limits)[0] is False
