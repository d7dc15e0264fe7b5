"""The finish's edge list by the CSR rows outside L_max (``core.driver``).

On a symmetric graph ``_prep_sampled`` marks the rows of the senders outside
L_max, and ``_finish_edges`` adds back the reverse of each marked edge into
L_max. These tests hold that to the rule it replaces, where an edge is kept
unless both its endpoints are in L_max: the same mask over the rows, the
same edge multiset, and the same labels on every placement. The rule needs
both directions of every edge stored, so ``build_graph(symmetrize=False)``
refuses input stored one way.
"""

import collections
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ConnectIt, VariantSpec
from repro.core import driver
from repro.core.sampling import make_sampler
from repro.graphs import build_graph, components_oracle
from repro.graphs import generators as gen

VARIANT = "kout_hybrid_k2+uf_sync_full"


GRAPHS = {
    # edges among vertices 10..29 only: isolated rows before, among and
    # after them
    "isolated": lambda rng: build_graph(rng.integers(10, 30, size=(25, 2)),
                                        60, pad_multiple=16),
    "random": lambda rng: build_graph(rng.integers(0, 40, size=(70, 2)), 40,
                                      pad_multiple=64),
    "m_eq_m_pad": lambda rng: build_graph(rng.integers(0, 30, size=(40, 2)),
                                          30, pad_multiple=1),
    "m_zero": lambda rng: build_graph(np.zeros((0, 2), np.int64), 12),
}

FLAGS = {
    "random": lambda rng, n: rng.random(n + 1) < 0.5,
    "every_vertex_in_lmax": lambda rng, n: np.zeros(n + 1, bool),
    "none_in_lmax": lambda rng, n: np.ones(n + 1, bool),
}


@pytest.mark.parametrize("flags", sorted(FLAGS))
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_rows_mask_is_the_sender_flag(gname, flags):
    rng = np.random.default_rng(3)
    g = GRAPHS[gname](rng)
    if gname == "m_eq_m_pad":
        assert g.m == g.m_pad
    if gname == "m_zero":
        assert g.m == 0
    flag = FLAGS[flags](rng, g.n)
    got = np.asarray(driver._rows_of(jnp.asarray(flag), g.indptr, g.m_pad))
    s = np.asarray(g.senders)
    np.testing.assert_array_equal(got, flag[s] & (s < g.n))


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("gname", sorted(GRAPHS))
def test_prep_sampled_mask_is_senders_outside_lmax(gname, seed):
    rng = np.random.default_rng(seed)
    g = GRAPHS[gname](rng)
    # three roots, 0, 1 and 2, and every other vertex on one of them
    P = np.append(rng.integers(0, 3, size=g.n), g.n).astype(np.int32)
    P[:3] = np.arange(3)
    _, keep, lmax, _ = driver._prep_sampled(jnp.asarray(P), g)
    s = np.asarray(g.senders)
    np.testing.assert_array_equal(np.asarray(keep),
                                  (P[s] != int(lmax)) & (s < g.n))


FAMILIES = {
    "rmat": lambda: gen.rmat(512, 3000, seed=4),
    "ba": lambda: gen.barabasi_albert(300, 2, seed=5),
    "path": lambda: gen.path(200),
    "star": lambda: gen.star(120),
    "planted": lambda: gen.planted_components(300, 5, 3.0, seed=6),
}


def _edge_multiset(s, r):
    return collections.Counter(zip(np.asarray(s).tolist(),
                                   np.asarray(r).tolist()))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_finish_list_is_the_two_endpoint_keep_set(family):
    g = FAMILIES[family]()
    P = make_sampler("kout", k=2, variant="hybrid")(g, jax.random.PRNGKey(1))
    P, keep, _, _ = driver._prep_sampled(P, g)
    s_new, r_new = driver._finish_edges(g, keep)
    # the rule this replaces, on the compressed labels (-1 = L_max)
    inside = np.asarray(P) == -1
    s, r = np.asarray(g.senders), np.asarray(g.receivers)
    old = ~(inside[s] & inside[r]) & (s < g.n)
    assert _edge_multiset(s_new, r_new) == _edge_multiset(s[old], r[old])

    ci = ConnectIt(VARIANT)
    labels = ci.connectivity(g, key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(labels), components_oracle(g))
    assert ci.stats.edges_finish == int(np.count_nonzero(old))


# -- one component joined to L_max by one edge ------------------------------

CLIQUE, SMALL, OTHER = range(0, 30), range(30, 35), range(35, 40)
JOIN = (5, 34)  # from L_max into the small component


def _joined_edges(both_ways_join: bool):
    pairs = [(i, j) for i in CLIQUE for j in CLIQUE if i != j]
    for part in (SMALL, OTHER):
        path = list(part)
        pairs += [(a, b) for a, b in zip(path, path[1:])]
        pairs += [(b, a) for a, b in zip(path, path[1:])]
    pairs.append(JOIN)
    if both_ways_join:
        pairs.append(JOIN[::-1])
    return np.array(pairs, np.int64)


def _sample_without_join(g, key):
    """A sample that merged each part but missed the joining edge."""
    del key
    P = np.arange(g.n + 1, dtype=np.int32)
    for part in (CLIQUE, SMALL, OTHER):
        P[list(part)] = part[0]
    return jnp.asarray(P)


def _finish(variant=VARIANT):
    return VariantSpec.parse(variant).build_finish()


def test_symmetric_join_takes_rows_and_one_reverse():
    g = build_graph(_joined_edges(True), 40)
    labels, stats = driver.run_connectivity(g, _sample_without_join,
                                            _finish())
    np.testing.assert_array_equal(np.asarray(labels), components_oracle(g))
    # the small and the other path, both ways, and the join both ways: the
    # join from L_max's row by its reverse
    assert stats.edges_finish == 2 * (4 + 4) + 2


ONE_WAY = {
    "lone_join": lambda: _joined_edges(False),
    "directed_path": lambda: np.array([(0, 1), (1, 2), (2, 3)], np.int64),
    # both directions, but one of them twice
    "uneven_multiplicity": lambda: np.array([(0, 1), (1, 0), (0, 1)],
                                            np.int64),
}


@pytest.mark.parametrize("edges", sorted(ONE_WAY))
def test_one_way_input_is_refused(edges):
    with pytest.raises(ValueError, match="symmetrize=False"):
        build_graph(ONE_WAY[edges](), 40, symmetrize=False, dedup=False)


def test_mirrored_input_builds_as_symmetrized():
    g = build_graph(_joined_edges(True), 40, symmetrize=False)
    ref = build_graph(_joined_edges(False), 40)
    assert (g.n, g.m) == (ref.n, ref.m)
    for field in ("senders", "receivers", "indptr", "indices"):
        np.testing.assert_array_equal(np.asarray(getattr(g, field)),
                                      np.asarray(getattr(ref, field)))


MESH_SCRIPT = """
import json, sys
import numpy as np
sys.path[:0] = [{tests!r}]
import jax
import test_lmax_rows as t
from repro.core.execution import make_backend
from repro.graphs import build_graph, components_oracle
g = build_graph(t._joined_edges(True), 40)
oracle = components_oracle(g)
out = {{"devices": jax.device_count()}}
for exec_str in ("replicated(x)", "sharded(x)"):
    labels, st = make_backend(exec_str).connectivity(
        g, t._sample_without_join, t._finish())
    out[exec_str] = dict(
        equal=bool((np.asarray(labels) == oracle).all()),
        finish=st.edges_finish, per_device=list(st.edges_per_device))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_runs():
    """Both mesh placements on 8 forced host devices, in a process of its
    own (the device count is fixed when JAX starts)."""
    tests = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join([str(tests.parent / "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT.format(tests=str(tests))],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("exec_str", ["replicated(x)", "sharded(x)"])
def test_mesh_placements_take_the_join(mesh_runs, exec_str):
    assert mesh_runs["devices"] == 8
    run = mesh_runs[exec_str]
    assert run["equal"]
    assert run["finish"] == 2 * (4 + 4) + 2
    assert sum(run["per_device"]) == run["finish"]
