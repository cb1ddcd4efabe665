"""``Graph.sssp`` (frontier Bellman-Ford on ``min_plus``) against the plain
float64 Bellman-Ford in numpy of ``bench/reference_sssp.py``: on an RMAT graph with seeded uniform
weights, a path, a grid with zero weights and a disconnected graph;
bitwise the same across the dispatch arms, backends and residencies; a
``[K]`` call equal to K scalar calls; unit weights equal to BFS levels; and
a clear refusal of a graph without weights."""
import numpy as np
import pytest

import repro
from bench import reference_sssp
from repro.algs import UNREACHED
from repro.core import ExecutionPolicy
from repro.graph import csr
from repro.graph.generators import rmat

CHUNK = 64


def bellman_ford(g: csr.Graph, source: int) -> np.ndarray:
    return reference_sssp.bellman_ford(g.indptr, g.indices, g.weights, source)


def _weighted_rmat():
    g = rmat(9, edge_factor=8, seed=11)
    src, dst = g.edges()
    w = np.random.default_rng(3).random(src.size, dtype=np.float32)
    return csr.from_edges(src, dst, n=g.n, weights=w, symmetrize=True)


def _path():
    n = 40
    v = np.arange(n - 1)
    w = np.random.default_rng(4).random(n - 1, dtype=np.float32) + 0.5
    return csr.from_edges(v, v + 1, n=n, weights=w)


def _grid_with_zero_weights():
    side = 12
    idx = np.arange(side * side).reshape(side, side)
    src = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    dst = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    w = np.random.default_rng(5).random(src.size, dtype=np.float32)
    w[::3] = 0.0
    return csr.from_edges(src, dst, n=side * side, weights=w, symmetrize=True)


def _disconnected():
    rng = np.random.default_rng(6)
    src = rng.integers(0, 30, 120)
    dst = rng.integers(0, 30, 120)
    src = np.concatenate([src, 40 + rng.integers(0, 20, 60)])
    dst = np.concatenate([dst, 40 + rng.integers(0, 20, 60)])
    w = rng.random(src.size, dtype=np.float32)
    return csr.from_edges(src, dst, n=64, weights=w, symmetrize=True)


GRAPHS = {"rmat": _weighted_rmat, "path": _path,
          "grid_zero_weights": _grid_with_zero_weights,
          "disconnected": _disconnected}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph(request):
    return request.param, GRAPHS[request.param]()


@pytest.fixture(scope="module")
def weighted():
    return _weighted_rmat()


def _check(got, ref):
    got = np.asarray(got, np.float64)
    assert np.array_equal(np.isfinite(got), np.isfinite(ref))
    both = np.isfinite(ref)
    assert np.all(got[both & (ref == 0)] == 0)
    pos = both & (ref > 0)
    assert np.all(np.abs(got[pos] - ref[pos]) <= 1e-5 * ref[pos])


def test_sssp_matches_bellman_ford(graph):
    name, h = graph
    g = repro.Graph(h, chunk_size=CHUNK)
    sources = [0, h.n // 2] if name != "disconnected" else [0, 45]
    for s in sources:
        res = g.sssp(s)
        assert res.values.shape == (h.n,)
        assert res.values.dtype == np.float32
        _check(res.values, bellman_ford(h, s))
    if name == "disconnected":
        assert np.isinf(np.asarray(g.sssp(0).values)[40:]).all()
    if name == "grid_zero_weights":
        # A zero-weight edge out of the source reaches at distance 0.
        d = np.asarray(g.sssp(0).values)
        assert np.count_nonzero(d == 0) > 1


POLICIES = {
    "p2p_scan": ExecutionPolicy(),
    "dense_scan": ExecutionPolicy(switch_fraction=None),
    "p2p_compact": ExecutionPolicy(backend="compact"),
    "dense_compact": ExecutionPolicy(backend="compact", switch_fraction=None),
    "p2p_capped": ExecutionPolicy(chunk_cap=8),
    "p2p_adaptive": ExecutionPolicy(adaptive_cap=True),
    "host_p2p": ExecutionPolicy(residency="host", stream_buffer=4),
    "host_dense": ExecutionPolicy(residency="host", stream_buffer=4,
                                  switch_fraction=None),
    "host_compact": ExecutionPolicy(residency="host", stream_buffer=4,
                                    backend="compact", chunk_cap=8),
}


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_sssp_bitwise_across_execution(weighted, policy):
    g = repro.Graph(weighted, chunk_size=CHUNK)
    base = g.sssp(7, policy=ExecutionPolicy(switch_fraction=None))
    res = g.sssp(7, policy=POLICIES[policy])
    assert np.array_equal(np.asarray(res.values), np.asarray(base.values))
    assert int(res.supersteps) == int(base.supersteps)
    assert int(res.state.improved) == int(base.state.improved)


@pytest.mark.parametrize("residency", ["device", "host"])
def test_sssp_batch_equals_scalar_calls(weighted, residency):
    g = repro.Graph(weighted, chunk_size=CHUNK)
    pol = ExecutionPolicy(residency=residency, stream_buffer=4)
    keys = [7, 100, 7, 300]
    batch = g.sssp(np.asarray(keys), policy=pol)
    assert batch.values.shape == (weighted.n, len(keys))
    for q, k in enumerate(keys):
        solo = g.sssp(k, policy=pol)
        assert np.array_equal(np.asarray(batch.values[:, q]),
                              np.asarray(solo.values))
        assert int(batch.query_supersteps[q]) == int(solo.supersteps)


def test_unit_weights_equal_bfs_levels():
    h = rmat(8, edge_factor=6, seed=2, symmetrize=True)
    # from_csr keeps every edge as it is (from_edges would sum the weights
    # of duplicate pairs).
    g = repro.Graph.from_csr(h.indptr, h.indices, weights=np.ones(h.m),
                             chunk_size=CHUNK)
    for s in (0, 17):
        levels = np.asarray(g.bfs(s).values)
        expect = np.where(levels == UNREACHED, np.inf,
                          levels.astype(np.float32))
        assert np.array_equal(np.asarray(g.sssp(s).values), expect)


def test_graph_without_weights_raises():
    h = rmat(6, edge_factor=4, seed=1, symmetrize=True)
    g = repro.Graph(h, chunk_size=CHUNK)
    with pytest.raises(ValueError, match="weights="):
        g.sssp(0)
    with pytest.raises(ValueError, match="weights="):
        repro.run_program(g.device(), repro.SSSPProgram(), seeds=[0])
