"""The GCN cell reads the same as before its model, graph kind and loop
became files of their own: at ``tiny(gcn3-arxiv)``, on two seeds, the
graph arrays, the inputs, the kept logits of four passes, the compared
numbers and the work counts equal those recorded from the harness that
held the GCN itself (commit cda75e4). A digest is the array's dtype, shape
and the first 16 hex digits of the SHA-256 of its bytes."""
import hashlib

import jax
import numpy as np
import pytest

from bench import check, system
from bench.tests.tiny import tiny_cell

PARENT = {
    2**31 + 77: {
        "graph": ["int64[3001]:4c6e234d5c5f5d54",
                  "int64[23000]:53db34282cc74b7a",
                  "float32[23000]:6802facc3d281275"],
        "inputs": ["float32[4, 3000, 16]:50b1979c94ce8c26",
                   "float32[16, 32]:e0b72c42bbdccc44",
                   "float32[32, 8]:af7b2f4d0e12f4fd"],
        "kept": [3, "float32[3000, 8]:2ef863b56ce711fd"],
        "program": {"rel_err": 6.61633761407826e-08,
                    "max_err": 2.679623420015876e-07, "med_err": 0.0,
                    "row_err": 9.337005755959183e-08, "answers": 1},
        "control": {"rel_err": 6.723657196580457e-06,
                    "max_err": 5.3145864496981535e-06,
                    "med_err": 7.087315261809277e-06,
                    "row_err": 1.1374623833954134e-05, "answers": 1},
    },
    5: {
        "graph": ["int64[3001]:296cfec0967d436b",
                  "int64[23000]:7542136c3349cd6b",
                  "float32[23000]:448f6380ab48f3b6"],
        "inputs": ["float32[4, 3000, 16]:d918f246363e850c",
                   "float32[16, 32]:663e356b67f0fab7",
                   "float32[32, 8]:df36067fcb6fd9e2"],
        "kept": [2, "float32[3000, 8]:48233245f3cd7053"],
        "program": {"rel_err": 5.893573050913145e-08,
                    "max_err": 1.9304395079888825e-07, "med_err": 0.0,
                    "row_err": 7.359820284677373e-08, "answers": 1},
        "control": {"rel_err": 4.666703591468308e-06,
                    "max_err": 4.536532843773874e-06,
                    "med_err": 4.663750143876692e-06,
                    "row_err": 7.98764396443197e-06, "answers": 1},
    },
}
# The same for both seeds: every seed gets the same degrees.
WORK_OF_FOUR_PASSES = {"spmm": (7360000.0, 5408032.0),
                       "dense": (18432000.0, 4236288.0)}


def digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return (f"{a.dtype}{list(a.shape)}:"
            + hashlib.sha256(a.tobytes()).hexdigest()[:16])


@pytest.mark.parametrize("seed", sorted(PARENT))
def test_tiny_gcn_reads_as_before(seed):
    want = PARENT[seed]
    config, mix = tiny_cell("arxiv-fullgraph")
    loop = system.make_loop(config, mix, seed, jax.devices()[:1])
    try:
        loop.setup()
        xs, params = loop.model.make_inputs(config, loop.n, loop.k_sets,
                                            seed)
        assert [digest(a) for a in loop.graph] == want["graph"]
        assert [digest(xs)] + [digest(w) for w in params] == want["inputs"]
        for _ in range(loop.k_sets):
            loop.window(0.0)
        p, logits = loop.answers()
        assert [p, digest(logits)] == want["kept"]
        assert loop.attempted_failed() == (8, 0)
        work = loop.work()
        per_pass = loop.model.work(loop.graph, config)
    finally:
        loop.close()
    assert {k: (w.flops, w.bytes) for k, w in work.items()} == \
        WORK_OF_FOUR_PASSES
    assert {k: (4 * w.flops, 4 * w.bytes) for k, w in per_pass.items()} == \
        WORK_OF_FOUR_PASSES
    exact = loop.reference_pairs((p, logits), "highest")
    lower = loop.reference_pairs((p, logits), "high")
    assert check.compare(exact) == want["program"]
    assert check.compare((lo, ex, t) for (_, lo, t), (_, ex, _)
                         in zip(lower, exact)) == want["control"]
