"""The device generators and the device build of the program's Graph."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import graphs, reference  # noqa: E402

G500 = {"kind": "kronecker", "scale": 8, "edgefactor": 16, "a": 0.57,
        "b": 0.19, "c": 0.19}


def numpy_build(s, r, n):
    """Symmetrize, drop self-loops and dedup on the host."""
    e = np.concatenate([np.stack([s, r], 1), np.stack([r, s], 1)])
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(e, axis=0)          # sorted by (sender, receiver)
    indptr = np.zeros(n + 2, np.int64)
    indptr[1:n + 1] = np.cumsum(np.bincount(e[:, 0], minlength=n))
    indptr[n + 1] = indptr[n]
    return e, indptr


KRON = graphs.generator("kronecker")


@pytest.mark.parametrize("gen,seed", [
    (G500, 0), (G500, 2**31 + 7), ({**G500, "scale": 5, "edgefactor": 4}, 9),
])
def test_device_build_equals_numpy_build(gen, seed):
    from bench.harness import seed_key

    s, r, n = KRON.edges(gen, seed_key(seed))
    e, indptr = numpy_build(np.asarray(s), np.asarray(r), n)
    g = graphs.build(s, r, n=n, m_pad=KRON.m_pad(gen))
    assert g.m == e.shape[0] and g.m_pad == KRON.m_pad(gen)
    snd, rcv = np.asarray(g.senders), np.asarray(g.receivers)
    assert np.array_equal(snd[:g.m], e[:, 0])
    assert np.array_equal(rcv[:g.m], e[:, 1])
    assert (snd[g.m:] == n).all() and (rcv[g.m:] == n).all()
    assert np.array_equal(np.asarray(g.indptr), indptr)
    assert g.indices is g.receivers


def test_stream_is_a_prefix_of_the_generators_order():
    from bench.harness import seed_key

    key = seed_key(11)
    s, r, n = KRON.stream(G500, key, 300)
    assert n == 1 << G500["scale"] and s.shape == r.shape == (300,)
    assert (s != r).all()
    s2, r2, _ = KRON.stream(G500, key, 100)
    assert np.array_equal(s[:100], s2) and np.array_equal(r[:100], r2)


def test_unknown_generator_kind_is_refused():
    with pytest.raises(ValueError, match="no generator"):
        graphs.generator("no-such-kind")


def test_kronecker_quadrant_shares():
    """One level: a share A+D of edges are loops, B and C the two others,
    whatever the relabelling."""
    s, r = KRON.kronecker_edges(jax.random.key(1), m=1 << 17, scale=1,
                                  a=0.57, b=0.19, c=0.19)
    s, r = np.asarray(s), np.asarray(r)
    assert abs((s == r).mean() - 0.62) < 0.01
    assert abs(((s == 0) & (r == 1)).mean() - 0.19) < 0.01
    assert abs(((s == 1) & (r == 0)).mean() - 0.19) < 0.01


def test_same_seed_same_graph_and_fixed_slots():
    from bench.harness import seed_key

    a = KRON.edges(G500, seed_key(3))
    b = KRON.edges(G500, seed_key(3))
    c = KRON.edges(G500, seed_key(4))
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    ga = graphs.build(*a[:2], n=a[2], m_pad=KRON.m_pad(G500))
    gc = graphs.build(*c[:2], n=c[2], m_pad=KRON.m_pad(G500))
    assert ga.m_pad == gc.m_pad


def test_reference_labels_are_least_vertex_ids():
    lab = reference.edge_components(6, np.array([5, 1, 3]),
                                    np.array([1, 4, 2]))
    assert lab.tolist() == [0, 1, 2, 2, 1, 1]
    indptr = np.array([0, 1, 2, 2])
    assert reference.csr_components(indptr, np.array([1, 0])).tolist() \
        == [0, 0, 2]


def test_serve_reference_and_its_one_epoch_stale_control():
    """Inserts (0,1) at epoch 1, (1,2) at epoch 2, (3,4) at epoch 3. A query
    at epoch e is answered over the edges of epochs <= e, and the control
    over those of epochs <= e - 1."""
    from bench.harness import load_module

    serve = load_module(ROOT / "bench" / "drivers" / "serve.py")
    u, v = np.array([0, 1, 3]), np.array([1, 2, 4])
    ack = np.array([1, 2, 3])
    q = (np.array([0, 0, 3]), np.array([1, 2, 4]))
    queries = [(e, *q) for e in range(4)]
    now, before = serve.answers_by_epoch(6, u, v, ack, queries)
    assert [a.tolist() for a in now] == [
        [False, False, False], [True, False, False], [True, True, False],
        [True, True, True]]
    assert [a.tolist() for a in before] == [
        [False, False, False], [False, False, False], [True, False, False],
        [True, True, False]]
