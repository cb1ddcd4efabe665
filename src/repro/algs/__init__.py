"""The six Graphyti algorithms (paper §4.1–4.6), baseline + optimized.

Every BSP-loop algorithm is a :class:`~repro.core.VertexProgram` on the
shared :func:`~repro.core.run_program` driver; the bare functions
(``bfs_multi``, ``pagerank_push``, ...) are deprecated shims kept for
compatibility.  New code goes through the ``repro.Graph`` façade (or
``run_program`` directly for custom programs).
"""
from .betweenness import (
    BCBackwardProgram,
    BCForwardProgram,
    FusedBCProgram,
    bc_fused,
    bc_multisource,
    bc_unisource,
)
from .bfs import UNREACHED, BFSProgram, bfs_multi, bfs_uni
from .coreness import CorenessProgram, coreness
from .diameter import diameter_multisource, diameter_unisource
from .louvain import LouvainResult, louvain, modularity
from .pagerank import (
    PageRankPullProgram,
    PageRankPushProgram,
    pagerank_inmem,
    pagerank_pull,
    pagerank_push,
)
from .sssp import SSSPProgram
from .triangles import TriangleResult, count_triangles, triangles_blocked_mxu

__all__ = [
    "UNREACHED",
    "BCBackwardProgram",
    "BCForwardProgram",
    "BFSProgram",
    "CorenessProgram",
    "FusedBCProgram",
    "LouvainResult",
    "PageRankPullProgram",
    "PageRankPushProgram",
    "SSSPProgram",
    "TriangleResult",
    "bc_fused",
    "bc_multisource",
    "bc_unisource",
    "bfs_multi",
    "bfs_uni",
    "coreness",
    "count_triangles",
    "diameter_multisource",
    "diameter_unisource",
    "louvain",
    "modularity",
    "pagerank_inmem",
    "pagerank_pull",
    "pagerank_push",
    "triangles_blocked_mxu",
]
