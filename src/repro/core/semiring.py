"""Semirings for vertex-centric message combination.

FlashGraph combines vertex messages in per-thread queues; the TPU-native
equivalent is a segment reduction over edge blocks under a semiring
``(combine, edge_op)``.  Every Graphyti algorithm in ``repro.algs`` is an
instance:

  * PageRank            -> ``plus_times``   (y[dst] += x[src] * w)
  * BFS / diameter      -> ``or_and``       (y[dst] |= x[src]), bool lanes
  * SSSP-style levels   -> ``min_plus``     (y[dst] = min(y[dst], x[src]+w))
  * coreness decrements -> ``plus_times``   (degree deltas)
  * betweenness sigma   -> ``plus_times``   (path counts)
  * Louvain             -> ``plus_times``   (community weight aggregation)

On TPU the multi-source "bitmap" of the paper becomes a vector *lane*
dimension (bool[n, K]) rather than a packed word: the VPU reduces over lanes
for free, whereas bit-twiddling packed words fights the ISA.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp

__all__ = ["Semiring", "PLUS_TIMES", "MIN_PLUS", "MAX_TIMES", "OR_AND"]


@dataclasses.dataclass(frozen=True)
class Semiring:
    """``y[k] = combine(y[k], edge_op(x[gather], w))`` over edges.

    Attributes:
      name: display name.
      combine: one of ``add | min | max`` — the scatter reduction. ``max`` on
        bool implements logical OR.
      identity: identity element of ``combine`` (fills padding lanes and the
        sentinel vertex slot ``n``).
      edge_op: maps (gathered vertex value, edge weight) -> contribution.
    """

    name: str
    combine: str
    identity: float | bool
    edge_op: Callable[[jnp.ndarray, Optional[jnp.ndarray]], jnp.ndarray]

    def scatter(self, y: jnp.ndarray, keys: jnp.ndarray, contrib: jnp.ndarray):
        """Scatter-combine ``contrib`` into ``y`` at ``keys`` (rows)."""
        at = y.at[keys]
        if self.combine == "add":
            return at.add(contrib)
        if self.combine == "min":
            return at.min(contrib)
        if self.combine == "max":
            return at.max(contrib)
        raise ValueError(f"unknown combine {self.combine!r}")

    def combine_elem(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Elementwise form of the scatter reduction (same dispatch)."""
        if self.combine == "add":
            return a + b
        if self.combine == "min":
            return jnp.minimum(a, b)
        if self.combine == "max":
            return jnp.maximum(a, b)
        raise ValueError(f"unknown combine {self.combine!r}")

    def neutral_like(self, x: jnp.ndarray, n_rows: int) -> jnp.ndarray:
        """An identity-filled output buffer with ``n_rows`` rows."""
        shape = (n_rows,) + x.shape[1:]
        return jnp.full(shape, self.identity, dtype=x.dtype)

    def mask_lanes(self, x: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
        """Identity-mask ``x`` per (vertex, lane).

        The batched multi-source path fetches edges for the *union* of the
        per-query frontiers; slots whose own lane is inactive must still
        contribute the ``combine`` identity so each query's result is
        exactly what its solo run would produce.  ``active`` broadcasts
        against ``x`` (bool[n, Q] against value[n, Q]).
        """
        return jnp.where(active, x, jnp.asarray(self.identity, x.dtype))


def _per_row(w, xv):
    """Edge weights ``w`` (one per gathered row) broadcast over the
    trailing query lanes of ``xv`` (``[E]`` against ``[E, K]``)."""
    w = jnp.asarray(w)
    return w.reshape(w.shape + (1,) * (jnp.ndim(xv) - w.ndim))


def _times(xv, w):
    return xv if w is None else xv * _per_row(w, xv)


def _plus(xv, w):
    return xv if w is None else xv + _per_row(w, xv)


def _ident(xv, w):
    return xv


PLUS_TIMES = Semiring("plus_times", combine="add", identity=0.0, edge_op=_times)
MIN_PLUS = Semiring("min_plus", combine="min", identity=jnp.inf, edge_op=_plus)
MAX_TIMES = Semiring("max_times", combine="max", identity=-jnp.inf, edge_op=_times)
# Logical OR over bool lanes: max(False, x) == x, max(True, _) == True.
OR_AND = Semiring("or_and", combine="max", identity=False, edge_op=_ident)
