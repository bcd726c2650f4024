"""The benchmark's own graph data: the normalisation agrees with the
program's today, and the generator keeps its stated shape."""
import numpy as np

from bench import system
from bench.data import graphs as data

CFG = {"kind": "power_law", "nodes": 2000, "edges": 15000, "max_degree": 400}


def test_normalisation_matches_the_program_copy():
    from repro.core.graph import CSRGraph, gcn_normalize
    g = data.power_law_graph(2000, 15000, 400, data.rng_for(7, 0))
    ours = data.gcn_normalize(g)
    theirs = gcn_normalize(CSRGraph(*g, 2000))
    for a, b in zip(ours, (theirs.rowptr, theirs.colidx, theirs.values)):
        np.testing.assert_array_equal(a, b)


def test_degrees_are_bounded_and_exact():
    deg = data.power_law_degrees(169343, 1166243, 13161)
    assert deg.sum() == 1166243 and deg.min() == 1 and deg.max() <= 13161
    assert np.median(deg) < 10 < np.percentile(deg, 99)


def test_simple_graph_with_the_stated_counts():
    rowptr, colidx, _ = data.power_law_graph(2000, 15000, 400,
                                             data.rng_for(2**31 + 3, 0))
    src = np.repeat(np.arange(2000), np.diff(rowptr))
    assert len(colidx) == 15000 and np.diff(rowptr).max() <= 400
    assert not np.any(src == colidx)                       # no self loop
    assert len(np.unique(src * 2000 + colidx)) == 15000    # no repeated edge


def test_same_seed_same_graph():
    a = data.power_law_graph(500, 3000, 100, data.rng_for(12_345_678_901, 0))
    b = data.power_law_graph(500, 3000, 100, data.rng_for(12_345_678_901, 0))
    c = data.power_law_graph(500, 3000, 100, data.rng_for(12_345_678_902, 0))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])


def test_every_seed_gets_the_same_degrees():
    """The cell's graphs differ by seed in structure, not in work: one
    degree sequence, dealt to other nodes."""
    (a,), (b,) = (system.build_graphs(CFG, s) for s in (1, 2**31 + 5))
    assert len(a[1]) == len(b[1])
    np.testing.assert_array_equal(np.sort(np.diff(a[0])),
                                  np.sort(np.diff(b[0])))
    assert not np.array_equal(np.diff(a[0]), np.diff(b[0]))
