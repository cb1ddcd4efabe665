"""Graph500 Kronecker graph with SSSP weights, generated on the device.

The tuples are Graph500's (graph500.org specification v3, Kronecker
generator), drawn exactly as ``graph500_kronecker`` draws them: from the
configuration's fixed ``graph_seed``, one initiator quadrant per level, and
the labels scrambled by the permutation the run's seed draws.  Kernel 3
(SSSP) adds one weight per tuple, uniform in [0, 1), drawn from
``graph_seed`` as well, so every seed serves the same weighted graph under
other labels.

The benchmark serves the undirected graph: both directions of a tuple get
its weight, self-loops are dropped, and of duplicate (src, dst) pairs the
least weight is kept.  Shortest distances on that graph are exactly those
on the multigraph of the tuples.  One device sort by (src, dst, weight)
puts the least weight first in each duplicate run; only the compaction of
the sorted columns runs on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import Graph as HostGraph

from bench.generators.graph500_kronecker import seed_key

# What the graph is, as a configuration states it.
PRODUCES = {"symmetric": True, "self_loops": "dropped",
            "duplicates": "least_weight", "weights": "uniform_0_1"}

# The weights' stream of the tuple key: the tuple draw folds in the level
# indices 0 .. scale - 1, so any number past them is a stream of its own.
WEIGHT_STREAM = 1 << 16


def kronecker_tuples(k_tuples, *, scale: int, edgefactor: int, a: float,
                     b: float, c: float):
    """Graph500's ``edgefactor * 2**scale`` Kronecker tuples ``(src, dst)``
    of the key ``k_tuples``, before the labels are scrambled: per level the
    source bit is 1 with probability ``C + D`` and the destination bit is
    drawn conditionally on it (the draw of ``graph500_kronecker``)."""
    m = edgefactor << scale
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
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


@functools.partial(jax.jit, static_argnames=("scale", "edgefactor", "a",
                                             "b", "c"))
def _sorted_weighted_edges(k_tuples, k_labels, *, scale: int,
                           edgefactor: int, a: float, b: float, c: float):
    """Symmetrized tuples' destinations and weights sorted by (src, dst,
    weight), the mask of the ones kept (no self-loop, first, so lightest,
    of each duplicate run), the CSR row pointers of the kept ones, and
    each label's unscrambled id."""
    n = 1 << scale
    src, dst = kronecker_tuples(k_tuples, scale=scale, edgefactor=edgefactor,
                                a=a, b=b, c=c)
    w = jax.random.uniform(jax.random.fold_in(k_tuples, WEIGHT_STREAM),
                           src.shape, jnp.float32)
    perm = jax.random.permutation(k_labels, n).astype(jnp.int32)
    unscrambled = jnp.zeros(n, jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))
    src, dst = perm[src], perm[dst]
    src, dst, w = (jnp.concatenate([src, dst]), jnp.concatenate([dst, src]),
                   jnp.concatenate([w, w]))
    src, dst, w = jax.lax.sort((src, dst, w), num_keys=3)
    first = jnp.concatenate([
        jnp.ones(1, bool), (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
    keep = first & (src != dst)
    kept_before = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(keep, dtype=jnp.int32)])
    row_start = jnp.searchsorted(src, jnp.arange(n + 1, dtype=jnp.int32))
    return dst, w, keep, kept_before[row_start], unscrambled


def weighted_kronecker_csr(graph_seed: int, seed: int, *, scale: int,
                           edgefactor: int, a: float, b: float, c: float):
    """``(indptr int64[n+1], indices int32[m], weights float32[m],
    unscrambled int32[n])`` of the symmetric, loop-free Kronecker graph of
    ``graph_seed`` with the least weight of each duplicate, under the
    labels of ``seed``; rows sorted by destination."""
    dst, w, keep, indptr, unscrambled = jax.device_get(
        _sorted_weighted_edges(seed_key(graph_seed), seed_key(seed),
                               scale=scale, edgefactor=edgefactor, a=a, b=b,
                               c=c))
    return indptr.astype(np.int64), dst[keep], w[keep], unscrambled


def generate(seed: int, config: dict) -> tuple[HostGraph, np.ndarray]:
    """The configuration's weighted graph under the labels of ``seed``, and
    each label's unscrambled id."""
    indptr, indices, weights, unscrambled = weighted_kronecker_csr(
        config["graph_seed"], seed, scale=config["scale"],
        edgefactor=config["edgefactor"], a=config["A"], b=config["B"],
        c=config["C"])
    # Symmetric with symmetric weights: the in-edge view is the out-edge
    # view.
    graph = HostGraph(n=indptr.shape[0] - 1, indptr=indptr, indices=indices,
                      weights=weights, in_indptr=indptr, in_indices=indices,
                      in_weights=weights)
    return graph, unscrambled
