"""Spanning forest (paper §3.4 / Algorithm 2): size, acyclicity, span."""

import numpy as np
import pytest

from conftest import partition_equiv
from repro.core import spanning_forest
from repro.graphs import components_oracle
from repro.graphs import generators as gen

GRAPHS = {
    "planted": lambda: gen.planted_components(150, 4, 4.0, seed=1),
    "rmat": lambda: gen.rmat(200, 700, seed=2),
    "torus": lambda: gen.torus((4, 4, 4)),
    "star": lambda: gen.star(40),
    "ba": lambda: gen.barabasi_albert(300, 2, seed=5),
    "path": lambda: gen.path(200),
}


def _check_forest(g, edges):
    oracle = components_oracle(g)
    ncomp = len(set(oracle.tolist()))
    assert len(edges) == g.n - ncomp, (len(edges), g.n - ncomp)
    parent = list(range(g.n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, v in edges:
        ru, rv = find(int(u)), find(int(v))
        assert ru != rv, "cycle in forest"
        parent[rv] = ru
    lab = np.array([find(i) for i in range(g.n)])
    assert partition_equiv(lab, oracle), "forest does not span"
    # every forest edge must be a real graph edge
    real = set(zip(np.asarray(g.senders)[: g.m].tolist(),
                   np.asarray(g.receivers)[: g.m].tolist()))
    for u, v in edges:
        assert (int(u), int(v)) in real or (int(v), int(u)) in real


@pytest.mark.parametrize("gname", sorted(GRAPHS))
@pytest.mark.parametrize("sampler", [None, "kout", "bfs", "ldd"])
def test_spanning_forest(gname, sampler):
    g = GRAPHS[gname]()
    edges = spanning_forest(g, sample=sampler)
    _check_forest(g, edges)
