"""Graph500 Kronecker graph, generated on the device.

The edge tuples follow the Graph500 reference generator (graph500.org,
"Graph 500 Benchmarks" specification, Kronecker generator): for each of
``scale`` levels, the source bit is 1 with probability ``C + D`` and the
destination bit is drawn conditionally on it, so the quadrant probabilities
are the initiator (A, B, C, D = 1 - A - B - C).  Vertex labels are then
scrambled by a random permutation.  ``edgefactor * 2**scale`` tuples are
drawn.

The tuples are drawn from the configuration's fixed ``graph_seed``, as an
LDBC Graphalytics ``graph500-*`` dataset is one fixed graph; the run's
seed draws the scrambling permutation.  Every seed therefore serves the
same graph under other labels: the same work, laid out in other chunks.

The benchmark serves the undirected graph: every tuple is added in both
directions, self-loops are dropped and duplicates removed by one device
sort.  Only the compaction of the sorted edge list runs on the host, after
the sorted columns are copied back (about 0.3 GB at scale 21).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import Graph as HostGraph

# What the graph is, as a configuration states it.
PRODUCES = {"symmetric": True, "self_loops": "dropped",
            "duplicates": "dropped"}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that depends on all bits of a non-negative ``seed``, also
    past 32 bits (``PRNGKey`` alone keeps the low 32 without x64)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.partial(jax.jit, static_argnames=("scale", "edgefactor", "a",
                                             "b", "c"))
def _sorted_edges(k_tuples, k_labels, *, scale: int, edgefactor: int,
                  a: float, b: float, c: float):
    """Symmetrized tuples' destinations sorted by (src, dst), the mask of
    the ones kept (no self-loop, first of each duplicate run), the CSR
    row pointers of the kept ones, and each label's unscrambled id."""
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def level(i, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(k_tuples, i), (2, m))
        src_bit = r[0] > ab
        dst_bit = r[1] > jnp.where(src_bit, c_norm, a_norm)
        return ((src << 1) | src_bit.astype(jnp.int32),
                (dst << 1) | dst_bit.astype(jnp.int32))

    zero = jnp.zeros(m, jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_labels, n).astype(jnp.int32)
    unscrambled = jnp.zeros(n, jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))
    src, dst = perm[src], perm[dst]
    src, dst = (jnp.concatenate([src, dst]), jnp.concatenate([dst, src]))
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    first = jnp.concatenate([
        jnp.ones(1, bool), (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
    keep = first & (src != dst)
    # indptr[v] counts the kept tuples before the first tuple of row v.
    kept_before = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(keep, dtype=jnp.int32)])
    row_start = jnp.searchsorted(src, jnp.arange(n + 1, dtype=jnp.int32))
    return dst, keep, kept_before[row_start], unscrambled


def kronecker_csr(graph_seed: int, seed: int, *, scale: int,
                  edgefactor: int, a: float, b: float, c: float
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(indptr int64[n+1], indices int32[m], unscrambled int32[n])`` of
    the symmetric, deduplicated, loop-free Kronecker graph of
    ``graph_seed`` under the labels of ``seed``; rows sorted.
    ``unscrambled[v]`` is label ``v``'s vertex in the graph before the
    labels were scrambled, the same for every ``seed``."""
    dst, keep, indptr, unscrambled = jax.device_get(_sorted_edges(
        seed_key(graph_seed), seed_key(seed), scale=scale,
        edgefactor=edgefactor, a=a, b=b, c=c))
    return indptr.astype(np.int64), dst[keep], unscrambled


def generate(seed: int, config: dict) -> tuple[HostGraph, np.ndarray]:
    """The configuration's graph under the labels of ``seed``, and each
    label's unscrambled id (see :func:`kronecker_csr`)."""
    indptr, indices, unscrambled = kronecker_csr(
        config["graph_seed"], seed, scale=config["scale"],
        edgefactor=config["edgefactor"], a=config["A"], b=config["B"],
        c=config["C"])
    # The graph is symmetric: the in-edge view is the out-edge view.
    graph = HostGraph(n=indptr.shape[0] - 1, indptr=indptr, indices=indices,
                      in_indptr=indptr, in_indices=indices)
    return graph, unscrambled
