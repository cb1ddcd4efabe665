"""Plain numpy references for the benchmark's jobs, independent of ``repro``.

``bfs_levels`` is the level-synchronous BFS of ``chip_smoke.py``; the
PageRank step is the same arithmetic as its ``pagerank_residual``: dangling
(degree-0) vertices send nothing, and every vertex receives the teleport
term ``(1 - c) / n``.
"""
from __future__ import annotations

import numpy as np

UNREACHED = np.iinfo(np.int32).max


def bfs_levels(indptr: np.ndarray, indices: np.ndarray,
               root: int) -> np.ndarray:
    """Level-synchronous BFS over CSR out-edges: int32 hop counts,
    ``UNREACHED`` where the root never arrives."""
    n = indptr.shape[0] - 1
    dist = np.full(n, UNREACHED, np.int32)
    dist[root] = 0
    frontier = np.array([root], np.int64)
    level = 0
    while frontier.size:
        level += 1
        starts, counts = indptr[frontier], np.diff(indptr)[frontier]
        offsets = np.repeat(starts - np.cumsum(counts) + counts, counts)
        nbrs = indices[offsets + np.arange(counts.sum())]
        seen = np.zeros(n, bool)
        seen[nbrs] = True
        frontier = np.flatnonzero(seen & (dist == UNREACHED))
        dist[frontier] = level
    return dist


def keep(x: np.ndarray) -> np.ndarray:
    """The identity: vectors kept in float64."""
    return x


def pagerank_iterates(in_indptr: np.ndarray, in_indices: np.ndarray,
                      out_degree: np.ndarray, *, damping: float, iters: int,
                      store=keep) -> list[np.ndarray]:
    """The first ``iters`` Jacobi iterates of pull PageRank from the uniform
    vector: ``R <- (1 - c)/n + c * A^T D^-1 R``, in float64, with the
    in-edges as CSR (vertex ``v`` gathers ``contrib[u]`` for every ``u`` in
    its in-row) and ``D`` the out-degrees.

    ``store`` rounds every vector the iteration keeps (ranks and the
    per-vertex contributions ``R / deg``) to a storage precision; the
    identity keeps float64.  Accumulation is float64 either way.
    Returns ``[R_1, ..., R_iters]``.
    """
    n = in_indptr.shape[0] - 1
    inv = np.where(out_degree > 0, 1.0 / np.maximum(out_degree, 1), 0.0)
    row = np.repeat(np.arange(n), np.diff(in_indptr))
    rank = store(np.full(n, 1.0 / n))
    out = []
    for _ in range(iters):
        contrib = store(rank * inv)
        acc = np.bincount(row, weights=contrib[in_indices], minlength=n)
        rank = store((1.0 - damping) / n + damping * acc)
        out.append(rank.astype(np.float64))
    return out


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Widest relative gap of ``got`` from ``ref`` over all vertices;
    ``inf`` on a shape mismatch or a non-finite value."""
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


def component_edges(indptr: np.ndarray, levels: np.ndarray) -> int:
    """Directed edges (twice the undirected ones) inside the component that
    ``levels`` reached."""
    deg = np.diff(indptr)
    return int(deg[levels != UNREACHED].sum())
