"""Graphyti-JAX: a semi-external-memory graph library (paper reproduction).

The public API is two layers:

  * :class:`repro.Graph` — the session façade: build once
    (``from_edges`` / ``from_csr``), run the library
    (``.bfs() .sssp() .pagerank() .betweenness() .coreness()
    .diameter() .triangles() .louvain()``) or your own algorithm
    (``.run(program)``), every call returning a
    :class:`~repro.core.ProgramResult` and driven by one
    :class:`~repro.core.ExecutionPolicy`.
  * :class:`repro.VertexProgram` + :func:`repro.run_program` — the
    extension point: ~30 lines of vertex logic inherit the full engine
    (push/pull direction optimization, density-adaptive dispatch, blocked
    Pallas backends, I/O accounting).  See ``examples/custom_program.py``.

Everything deeper (``repro.core`` engine primitives, ``repro.algs``
program classes, ``repro.graph`` host containers) stays importable for
power users.
"""
from .core import (
    CheckpointSpec,
    ExecutionPolicy,
    PolicyError,
    ResidencyError,
    FailurePlan,
    Frontier,
    IOStats,
    ProgramResult,
    VertexProgram,
    WorkQueue,
    run_program,
    run_supervised,
)
from .algs.sssp import SSSPProgram
from .graph.session import Graph

__all__ = [
    "CheckpointSpec",
    "ExecutionPolicy",
    "FailurePlan",
    "Frontier",
    "Graph",
    "IOStats",
    "PolicyError",
    "ProgramResult",
    "ResidencyError",
    "SSSPProgram",
    "VertexProgram",
    "WorkQueue",
    "run_program",
    "run_supervised",
]
