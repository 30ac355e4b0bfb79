"""Weakly connected components, labelled the way LDBC Graphalytics WCC and
the engine's ``cc`` program label them: every vertex gets the smallest vertex
id of its component.  Plain SciPy over the benchmark's own edge list."""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components


def min_labels(num_vertices: int, edges: np.ndarray) -> np.ndarray:
    """``[num_vertices]`` int64: the least vertex id in each vertex's
    component, edges taken as undirected."""
    edges = np.asarray(edges, np.int64)
    adj = coo_matrix((np.ones(len(edges), np.int8), (edges[:, 0], edges[:, 1])),
                     shape=(num_vertices, num_vertices))
    _, comp = connected_components(adj, directed=True, connection="weak")
    least = np.full(comp.max() + 1, num_vertices, np.int64)
    np.minimum.at(least, comp, np.arange(num_vertices, dtype=np.int64))
    return least[comp]


CHECK = "wrong_labels"  # vertices whose label differs from the reference
LIMIT = 0  # an exact comparison


def solve(num_vertices: int, edges: np.ndarray) -> np.ndarray:
    return min_labels(num_vertices, edges)


def compare(truth: np.ndarray, table: np.ndarray) -> int:
    """How many vertices the job labelled otherwise than the reference."""
    table = np.asarray(table)
    if table.shape != truth.shape:
        return len(truth)
    return int(np.count_nonzero(table.astype(np.int64) != truth))
