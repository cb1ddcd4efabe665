"""Single-source shortest paths: frontier Bellman-Ford under ``min_plus``.

Graph500's kernel 3 and an LDBC Graphalytics kernel.  Each superstep the
vertices whose distance fell in the previous one multicast their distance;
every out-edge ``(u, v, w)`` offers ``dist[u] + w`` to ``v``, the engine
combines the offers by ``min`` (:data:`~repro.core.semiring.MIN_PLUS`), and
``apply`` keeps ``min(dist, offer)``.  The search converges when no distance
falls.  With non-negative weights every vertex's distance is final after at
most ``n - 1`` improving supersteps; on a power-law graph a few dozen
suffice.

The program is label-correcting: a vertex may improve several times before
its distance is final, and each improvement re-sends its out-edges.  The
state's ``improved`` counter sums those improvements over all supersteps —
the redundancy a delta-stepping frontier would cut.

Distances are float32 with ``inf`` where a source never arrives.  Like
:class:`~repro.algs.bfs.BFSProgram`, the state carries a trailing lane per
source, so ``K`` searches share one edge stream.

The default policy is :class:`~repro.core.ExecutionPolicy`'s own: a
superstep whose frontier carries at most a tenth of the edges takes the
point-to-point gather at its static capacity (``vcap``/``ecap``, which
default to ``n`` and to that tenth of ``m``), and a heavier one the dense
chunk scan.  No compacted chunk scan is compiled unless the policy sets
``chunk_cap`` or ``adaptive_cap``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from ..core import ExecutionPolicy, Frontier, SemGraph, VertexProgram
from ..core.semiring import MIN_PLUS

__all__ = ["SSSPProgram", "SSSPState"]


class SSSPState(NamedTuple):
    dist: jnp.ndarray  # float32[n, K]
    frontier: jnp.ndarray  # bool[n, K] distance fell last superstep
    improved: jnp.ndarray  # int32 scalar: improvements, all supersteps


class SSSPProgram(VertexProgram):
    """K concurrent shortest-path searches over the weighted out-edges.

    ``seeds``: int32[K] source vertex ids.  ``values``: float32[n, K]
    distances, ``inf`` where a lane never arrives.  ``state.improved``
    counts the (vertex, lane) distance decreases over the whole run.
    """

    semiring = MIN_PLUS
    default_policy = ExecutionPolicy()

    def init(self, sg: SemGraph, seeds) -> SSSPState:
        # Under min_plus a missing weight is the edge_op identity, so a
        # graph without weights would give every distance as 0.
        weighted = (sg.host.weights is not None
                    if getattr(sg, "is_host_view", False) else sg.w is not None)
        if not weighted:
            raise ValueError(
                "sssp needs edge weights; build the graph with weights= "
                "(repro.Graph.from_edges(src, dst, weights=w) or from_csr)")
        sources = jnp.asarray(seeds, jnp.int32)
        n, K = sg.n, sources.shape[0]
        lanes = jnp.arange(K)
        dist = jnp.full((n, K), jnp.inf, jnp.float32).at[sources, lanes].set(0.0)
        frontier = jnp.zeros((n, K), bool).at[sources, lanes].set(True)
        return SSSPState(dist, frontier, jnp.zeros((), jnp.int32))

    def frontier(self, sg: SemGraph, s: SSSPState) -> Frontier:
        return Frontier(x=s.dist, active=s.frontier)

    def apply(self, sg: SemGraph, s: SSSPState, relaxed):
        fell = relaxed < s.dist
        dist = jnp.where(fell, relaxed, s.dist)
        improved = s.improved + jnp.sum(fell, dtype=jnp.int32)
        return SSSPState(dist, fell, improved), fell

    def finalize(self, sg: SemGraph, s: SSSPState) -> jnp.ndarray:
        return s.dist
