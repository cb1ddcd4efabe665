"""The shape × residency parity matrix of the ``repro.Graph`` façade.

One case is one façade call on one graph shape under one residency:

  * its values are compared with an independent reference — networkx for
    BFS levels and core numbers, ``bench/reference_sssp.bellman_ford`` for
    distances, ``bench/reference.pagerank_iterates`` (and networkx where
    no vertex is dangling) for ranks;
  * under ``residency='host'`` (``stream_buffer=2``, so every superstep
    stitches several batches) it is also compared with the device run of
    the same call by :func:`conftest.assert_result_parity`: bitwise
    values, the same supersteps, the same IOStats but ``host_bytes``.

The shapes are the ones the other tests do not cover: a path of 97
vertices (about 97 supersteps), a cycle whose 256 edges fill exactly two
128-record chunks, a star whose hub's 299 edges span three chunks, two
RMAT islands plus 16 isolated vertices, the benchmark's Kronecker shape
at scale 8, and a sparse Erdos-Renyi graph of many small components.
``tests/test_shapes_*.py`` split the shapes between them, so that no
module runs long on one worker; each module shares one session per shape
and one device run per (shape, program) through a :class:`ShapeMatrix`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import networkx as nx
import numpy as np

import repro
from bench import reference, reference_sssp
from conftest import assert_result_parity
from repro.algs import UNREACHED
from repro.core import ExecutionPolicy
from repro.graph import csr
from repro.graph.generators import (
    cycle_graph,
    erdos_renyi,
    path_graph,
    rmat,
    star_graph,
)

CHUNK = 128
RESIDENCIES = ("device", "host")
HOST = {"residency": "host", "stream_buffer": 2}
DAMPING = 0.85
PUSH_TOL = 1e-3  # Graph.pagerank's default tol


def _islands() -> csr.Graph:
    a = rmat(6, seed=1, symmetrize=True)
    b = rmat(6, seed=2, symmetrize=True)
    sa, da = a.edges()
    sb, db = b.edges()
    return csr.from_edges(np.concatenate([sa, sb + a.n]),
                          np.concatenate([da, db + a.n]),
                          n=a.n + b.n + 16)


SHAPES: dict[str, Callable[[], csr.Graph]] = {
    "path": lambda: path_graph(97),
    "cycle": lambda: cycle_graph(128),
    "star": lambda: star_graph(300),
    "islands": _islands,
    "kron8": lambda: rmat(8, edge_factor=16, symmetrize=True),
    "sparse": lambda: erdos_renyi(256, 256, symmetrize=True),
}

# Searches start at an end of the path and cycle, at a leaf of the star
# (the hub is then the second frontier), elsewhere at the vertex of
# highest degree.
_SOURCES = {"path": 0, "cycle": 0, "star": 1}


def source(shape: str, h: csr.Graph) -> int:
    if shape in _SOURCES:
        return _SOURCES[shape]
    return int(np.argmax(np.diff(h.indptr)))


def weighted(h: csr.Graph, seed: int = 0) -> csr.Graph:
    """``h`` with weights uniform in [0, 1) drawn from ``seed``, the same
    weight on both directions of an edge."""
    src, dst = h.edges()
    one_way = src < dst
    w = np.random.default_rng(seed).random(int(one_way.sum()),
                                           dtype=np.float32)
    return csr.from_edges(src[one_way], dst[one_way], n=h.n, weights=w,
                          symmetrize=True)


def _nx(h: csr.Graph) -> nx.Graph:
    G = nx.Graph()
    G.add_nodes_from(range(h.n))
    G.add_edges_from(zip(*h.edges()))
    return G


# ------------------------------------------------------------ references
def check_bfs(got, h: csr.Graph, s: int) -> None:
    ref = np.full(h.n, int(UNREACHED), np.int64)
    for v, d in nx.single_source_shortest_path_length(_nx(h), s).items():
        ref[v] = d
    assert np.array_equal(np.asarray(got), ref)


def check_sssp(got, h: csr.Graph, s: int) -> None:
    ref = reference_sssp.bellman_ford(h.indptr, h.indices, h.weights, s)
    got = np.asarray(got, np.float64)
    assert np.array_equal(np.isfinite(got), np.isfinite(ref))
    both = np.isfinite(ref)
    assert np.all(got[both & (ref == 0)] == 0)
    pos = both & (ref > 0)
    assert np.all(np.abs(got[pos] - ref[pos]) <= 1e-5 * ref[pos])


def _iterates(h: csr.Graph, iters: int) -> list[np.ndarray]:
    return reference.pagerank_iterates(h.in_indptr, h.in_indices,
                                       np.diff(h.indptr), damping=DAMPING,
                                       iters=iters)


def check_pull5(got, h: csr.Graph, s: int) -> None:
    """Five pull iterations from the uniform vector, as the PR cell's job."""
    err = reference.max_rel_err(np.asarray(got), _iterates(h, 5)[-1])
    assert err <= 1e-5, err


def check_push(got, h: csr.Graph, s: int) -> None:
    """Delta-push stops once every residual is at most ``tol / n``, so the
    residuals left sum to at most ``tol``; at damping c they move the
    fixed point by at most ``tol / (1 - c)`` in L1."""
    got = np.asarray(got, np.float64)
    refs = [_iterates(h, 300)[-1]]
    if np.all(np.diff(h.indptr) > 0):
        # networkx spreads a dangling vertex's rank over all vertices,
        # where Graphyti's PageRank lets it send nothing.
        pr = nx.pagerank(_nx(h), alpha=DAMPING, tol=1e-12, max_iter=1000)
        refs.append(np.array([pr[v] for v in range(h.n)]))
    for ref in refs:
        assert np.abs(got - ref).sum() <= PUSH_TOL / (1 - DAMPING)


def check_coreness(got, h: csr.Graph, s: int) -> None:
    core = nx.core_number(_nx(h))
    assert np.array_equal(np.asarray(got),
                          np.array([core[v] for v in range(h.n)]))


@dataclasses.dataclass(frozen=True)
class Program:
    call: Callable  # (Graph, source, policy) -> ProgramResult
    policy: ExecutionPolicy  # the device policy; host adds HOST
    check: Callable  # (values, host graph, source) -> None
    weighted: bool = False


_DENSE = ExecutionPolicy(switch_fraction=None)  # as in the BFS and PR cells
_GATED = ExecutionPolicy()  # the p2p arm behind its gate, as in the SSSP cell

PROGRAMS = {
    "bfs-dense": Program(lambda g, s, p: g.bfs(s, policy=p), _DENSE,
                         check_bfs),
    "bfs-gated": Program(lambda g, s, p: g.bfs(s, policy=p), _GATED,
                         check_bfs),
    "sssp-dense": Program(lambda g, s, p: g.sssp(s, policy=p), _DENSE,
                          check_sssp, weighted=True),
    "sssp-gated": Program(lambda g, s, p: g.sssp(s, policy=p), _GATED,
                          check_sssp, weighted=True),
    "pr-pull5": Program(lambda g, s, p: g.pagerank(
        mode="pull", tol=0.0, max_iters=5, policy=p), _DENSE, check_pull5),
    "pr-push": Program(lambda g, s, p: g.pagerank(policy=p), _GATED,
                       check_push),
    "coreness": Program(lambda g, s, p: g.coreness(policy=p), _GATED,
                        check_coreness),
}


def check_shape(matrix: "ShapeMatrix", shape: str) -> None:
    """The graph has the property its shape is in the matrix for."""
    h, _ = matrix.graph(shape, False)
    deg = np.diff(h.indptr)
    G = _nx(h)
    if shape == "path":
        far = nx.single_source_shortest_path_length(G, source(shape, h))
        assert max(far.values()) == h.n - 1
    elif shape == "cycle":
        assert h.m == 2 * CHUNK
    elif shape == "star":
        assert deg[0] == h.n - 1 > 2 * CHUNK
    elif shape == "islands":
        src, dst = h.edges()
        assert np.array_equal(src < 64, dst < 64)
        assert np.all(deg[-16:] == 0)
    elif shape == "kron8":
        assert h.n == 256 and deg.max() > 5 * deg.mean()
    elif shape == "sparse":
        assert nx.number_connected_components(G) > 16
    else:
        raise AssertionError(f"no property stated for shape {shape!r}")


class ShapeMatrix:
    """One session per (shape, weighted) and one device result per
    (shape, program), built on first use."""

    def __init__(self):
        self._graphs: dict = {}
        self._device: dict = {}

    def graph(self, shape: str, weights: bool):
        key = (shape, weights)
        if key not in self._graphs:
            h = SHAPES[shape]()
            if weights:
                h = weighted(h)
            self._graphs[key] = (h, repro.Graph(h, chunk_size=CHUNK))
        return self._graphs[key]

    def run(self, shape: str, name: str, residency: str):
        prog = PROGRAMS[name]
        h, g = self.graph(shape, prog.weighted)
        pol = prog.policy if residency == "device" else prog.policy.with_(**HOST)
        return h, prog.call(g, source(shape, h), pol)

    def device(self, shape: str, name: str):
        if (shape, name) not in self._device:
            self._device[shape, name] = self.run(shape, name, "device")[1]
        return self._device[shape, name]

    def check(self, shape: str, name: str, residency: str) -> None:
        if residency == "device":
            h, _ = self.graph(shape, PROGRAMS[name].weighted)
            res = self.device(shape, name)
        else:
            h, res = self.run(shape, name, "host")
            assert_result_parity(self.device(shape, name), res)
        PROGRAMS[name].check(res.values, h, source(shape, h))
