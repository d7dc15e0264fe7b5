"""The plain reference: connected components on the host with scipy.

It imports nothing of the program under test and reads only the edges that
the benchmark itself generated. Labels follow the program's documented
convention: each vertex is labelled with the least vertex id of its
component.
"""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import connected_components


def min_vertex_labels(comp: np.ndarray) -> np.ndarray:
    """Component ids -> the least vertex id of each component."""
    _, first, inverse = np.unique(comp, return_index=True,
                                  return_inverse=True)
    return first[inverse].astype(np.int32)


def csr_components(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Labels of a symmetric graph given as CSR rows ``0..n-1``.

    On a symmetric graph the strong components are the components, and
    scipy finds them from the CSR alone, with no transpose."""
    n = indptr.shape[0] - 1
    data = np.ones(indices.shape[0], np.int8)
    g = csr_matrix((data, indices, indptr), shape=(n, n))
    _, comp = connected_components(g, directed=True, connection="strong")
    return min_vertex_labels(comp)


def edge_components(n: int, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Labels of the undirected graph on ``n`` vertices with edges (u, v)."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    g = csr_matrix((np.ones(u.shape[0], np.int8), (u, v)), shape=(n, n))
    _, comp = connected_components(g, directed=False)
    return min_vertex_labels(comp)
