"""Plain references for the benchmark's SSSP jobs, independent of ``repro``.

Dijkstra's algorithm (``scipy.sparse.csgraph.dijkstra``) in float64 on the
host CSR decides ``correct``.  An edge of weight 0 is kept as an edge: a
``csr_matrix`` keeps its explicit zeros, and nothing here calls
``eliminate_zeros``.  A frontier Bellman-Ford in numpy, the algorithm the
program runs, serves the CPU tests (float64) and the bfloat16 control of
``calibrate_sssp.py``.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra


def weighted_csr(indptr: np.ndarray, indices: np.ndarray,
                 weights: np.ndarray) -> csr_matrix:
    """The graph as a float64 ``csr_matrix``, explicit zeros included."""
    n = indptr.shape[0] - 1
    return csr_matrix((np.asarray(weights, np.float64), indices, indptr),
                      shape=(n, n))


def distances(matrix: csr_matrix, source: int) -> np.ndarray:
    """float64 shortest distances from ``source`` along the out-edges,
    ``inf`` where it never arrives."""
    return dijkstra(matrix, directed=True, indices=int(source))


def keep(x: np.ndarray) -> np.ndarray:
    """The identity: values kept in float64."""
    return x


def bellman_ford(indptr: np.ndarray, indices: np.ndarray,
                 weights: np.ndarray, source: int, store=keep) -> np.ndarray:
    """Frontier Bellman-Ford over the CSR out-edges, in float64 with the
    weights and every distance it keeps rounded by ``store`` (the identity
    keeps float64); ``inf`` where ``source`` never arrives."""
    n = indptr.shape[0] - 1
    src = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
    w = store(np.asarray(weights, np.float64))
    dist = np.full(n, np.inf)
    dist[source] = 0.0
    frontier = np.zeros(n, bool)
    frontier[source] = True
    while frontier.any():
        e = frontier[src]
        relaxed = dist.copy()
        np.minimum.at(relaxed, indices[e], store(dist[src[e]] + w[e]))
        frontier = relaxed < dist
        dist = relaxed
    return dist


def component_edges(indptr: np.ndarray, dist: np.ndarray) -> int:
    """Directed edges (twice the undirected ones) inside the component that
    ``dist`` reached (its finite entries)."""
    deg = np.diff(indptr)
    return int(deg[np.isfinite(dist)].sum())


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Widest ``|got - ref| / ref`` over the vertices both reach with
    ``ref > 0``; ``inf`` where ``ref == 0`` and ``got`` is not exactly 0."""
    got = np.asarray(got, np.float64)
    both = np.isfinite(got) & np.isfinite(ref)
    if np.any(got[both & (ref == 0)] != 0):
        return float("inf")
    pos = both & (ref > 0)
    if not pos.any():
        return 0.0
    return float(np.max(np.abs(got[pos] - ref[pos]) / ref[pos]))


def reach_wrong(got: np.ndarray, ref: np.ndarray) -> int:
    """Vertices whose reachability (a finite distance) differs."""
    return int(np.sum(np.isfinite(np.asarray(got)) != np.isfinite(ref)))
