"""Coreness (k-core) decomposition — paper §4.2.

Principles P2 (*minimize messaging* — hybrid multicast/point-to-point) and
P3 (*algorithmically prune computation* — skip k levels that cannot remove
anything, because the next possible core value is at least the minimum
degree among the remaining vertices).

The benchmark triple reproducing Fig. 3:
  * ``messaging='p2p',    prune=False``  — the unoptimized baseline
  * ``messaging='dense',  prune=True``   — pruning alone
  * ``messaging='hybrid', prune=True``   — pruning + hybrid messaging

Works on undirected (symmetrized) graphs; the degree used is out-degree,
which equals total degree after symmetrization.

The peeling loop is a :class:`CorenessProgram` on the shared
:func:`~repro.core.run_program` driver.  Its ``gather`` override shows a
program shaping its own I/O: a superstep that removes nothing advances the
peeling level *without* touching the engine (a ``lax.cond`` skips the
multicast entirely), so empty rounds cost zero I/O — exactly the ledger
the pre-program implementation kept.  ``coreness`` is a deprecated shim;
new code goes through ``repro.Graph.coreness()``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core import (
    ExecutionPolicy,
    Frontier,
    IOStats,
    SemGraph,
    VertexProgram,
    legacy_policy,
    p2p_spmv,
    run_program,
    traverse,
)
from ..core.engine import p2p_caps
from ..core.semiring import PLUS_TIMES

__all__ = ["CorenessProgram", "coreness"]

_INT_MAX = jnp.iinfo(jnp.int32).max


class CoreState(NamedTuple):
    deg: jnp.ndarray  # int32[n] current (decremented) degree
    alive: jnp.ndarray  # bool[n]
    core: jnp.ndarray  # int32[n] assigned coreness (valid once removed)
    k: jnp.ndarray  # int32 current peeling level


class CorenessProgram(VertexProgram):
    """k-core peeling.  ``values``: int32[n] core numbers.

    Each superstep removes every live vertex with current degree <= k and
    multicasts degree decrements to its neighbors.  When a superstep
    removes nothing, k advances — to k+1 unpruned, or directly to
    ``min(deg[alive])`` with pruning (P3): intermediate k values cannot
    remove any vertex, so their supersteps (and their frontier scans) are
    pure waste.

    ``messaging`` keeps the Fig. 3 benchmark triple: 'dense' is pure
    multicast, 'p2p' always row-exact fetches, 'hybrid' the engine's
    density dispatch.  The policy refines the 'dense'/'hybrid' execution —
    peeling frontiers are usually tiny (the vertices that just dropped to
    degree k), so a ``chunk_cap`` routes mid-density removals through the
    compact scan (P2 paid in wall-clock, not just counters).
    """

    semiring = PLUS_TIMES

    def __init__(self, *, prune: bool = True, messaging: str = "hybrid"):
        assert messaging in ("dense", "p2p", "hybrid")
        self.prune = prune
        self.messaging = messaging

    def prepare_policy(self, sg: SemGraph, policy: ExecutionPolicy):
        pol = policy.with_(direction="out")
        if self.messaging == "dense":
            return pol.with_(switch_fraction=None)
        if pol.switch_fraction is None:  # no p2p arm, no caps to resolve
            return pol
        vcap, ecap, _ = p2p_caps(pol, sg.n, sg.m)
        return pol.with_(vcap=vcap, ecap=ecap)

    def init(self, sg: SemGraph, seeds) -> CoreState:
        return CoreState(
            deg=sg.out_degree.astype(jnp.int32),
            alive=jnp.ones(sg.n, bool),
            core=jnp.zeros(sg.n, jnp.int32),
            k=jnp.zeros((), jnp.int32),
        )

    def frontier(self, sg: SemGraph, s: CoreState) -> Frontier:
        removed = s.alive & (s.deg <= s.k)
        return Frontier(x=jnp.where(removed, -1.0, 0.0), active=removed)

    def gather(self, sg: SemGraph, s: CoreState, fr: Frontier, policy):
        """Push -1 along out-edges of removed vertices — but only when the
        round removes anything; an advance round does zero I/O."""

        def fetch(_):
            if self.messaging == "p2p":
                if getattr(sg, "is_host_view", False):
                    # The raw p2p gather has no host form; force the host
                    # dispatcher's p2p arm with the same hardcoded caps.
                    # Capacity-invariance makes values and IOStats match
                    # the direct call bitwise.
                    return traverse(
                        sg, fr.x, fr.active, PLUS_TIMES,
                        policy=policy.with_(switch_fraction=1.0, vcap=sg.n,
                                            ecap=max(int(sg.m), 1)),
                    )
                return p2p_spmv(sg, fr.x, fr.active, PLUS_TIMES,
                                direction="out", vcap=sg.n,
                                ecap=max(int(sg.m), 1))
            return traverse(sg, fr.x, fr.active, PLUS_TIMES, policy=policy)

        def skip(_):
            return jnp.zeros(sg.n), IOStats.zero()

        pred = jnp.any(fr.active)
        if isinstance(pred, jax.core.Tracer):
            return jax.lax.cond(pred, fetch, skip, None)
        # Eager (host-residency) driver: lax.cond would trace BOTH branches,
        # and a traced frontier cannot be streamed — take a Python branch.
        return fetch(None) if bool(pred) else skip(None)

    def apply(self, sg: SemGraph, s: CoreState, delta):
        removed = s.alive & (s.deg <= s.k)

        def remove(_):
            core = jnp.where(removed, s.k, s.core)
            alive = s.alive & ~removed
            deg = s.deg + delta.astype(jnp.int32)
            return CoreState(deg, alive, core, s.k)

        def advance(_):
            live_deg = jnp.where(s.alive, s.deg, _INT_MAX)
            next_k = jnp.min(live_deg) if self.prune else s.k + 1
            next_k = jnp.maximum(next_k, s.k + 1)
            return CoreState(s.deg, s.alive, s.core, next_k)

        s = jax.lax.cond(jnp.any(removed), remove, advance, None)
        return s, s.alive

    def converged(self, sg: SemGraph, s: CoreState, activated):
        return ~jnp.any(s.alive)

    def max_supersteps(self, sg: SemGraph) -> int:
        return 4 * sg.n + 64

    def finalize(self, sg: SemGraph, s: CoreState) -> jnp.ndarray:
        return s.core


def coreness(
    sg: SemGraph,
    *,
    prune: bool = True,
    messaging: str = "hybrid",
    switch_fraction: float | None = None,
    max_supersteps: int | None = None,
    chunk_cap: int | None = None,
    policy: Optional[ExecutionPolicy] = None,
) -> tuple[jnp.ndarray, IOStats, jnp.ndarray]:
    """Deprecated shim over :class:`CorenessProgram` — use
    ``repro.Graph.coreness()``.  Returns (core_number[n], IOStats,
    supersteps)."""
    pol = legacy_policy("coreness", "repro.Graph.coreness(policy=...)",
                        policy, None, chunk_cap=chunk_cap,
                        switch_fraction=switch_fraction)
    res = run_program(sg, CorenessProgram(prune=prune, messaging=messaging),
                      pol, max_supersteps=max_supersteps)
    return res.values, res.iostats, res.supersteps
