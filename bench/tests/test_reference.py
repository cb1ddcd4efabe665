"""The numpy references against ``repro`` at RMAT scale 10, for both
traffic kinds."""
import numpy as np
import pytest

import repro
from bench import reference
from bench.generators import graph500_kronecker as kronecker
from repro.graph.csr import Graph as HostGraph

PARAMS = dict(scale=10, edgefactor=16, a=0.57, b=0.19, c=0.19)


@pytest.fixture(scope="module")
def session():
    indptr, indices, _ = kronecker.kronecker_csr(1, 5, **PARAMS)
    host = HostGraph(n=indptr.shape[0] - 1, indptr=indptr, indices=indices,
                     in_indptr=indptr, in_indices=indices)
    return host, repro.Graph(host)


@pytest.mark.parametrize("residency", ["device", "host"])
def test_bfs_levels_match_repro(session, residency):
    host, g = session
    pol = repro.ExecutionPolicy(switch_fraction=None, residency=residency)
    deg = np.diff(host.indptr)
    for key in np.flatnonzero(deg > 0)[:3]:
        got = np.asarray(g.bfs(int(key), policy=pol).values)
        ref = reference.bfs_levels(host.indptr, host.indices, int(key))
        assert np.array_equal(got, ref)


def test_isolated_key_reaches_only_itself(session):
    host, _ = session
    key = int(np.flatnonzero(np.diff(host.indptr) == 0)[0])
    ref = reference.bfs_levels(host.indptr, host.indices, key)
    assert ref[key] == 0 and np.sum(ref != reference.UNREACHED) == 1
    assert reference.component_edges(host.indptr, ref) == 0


@pytest.mark.parametrize("iters", [1, 5])
def test_pull_pagerank_iterates_match_repro(session, iters):
    host, g = session
    res = g.pagerank(mode="pull", damping=0.85, tol=0.0, max_iters=iters)
    assert int(res.supersteps) == iters
    ref = reference.pagerank_iterates(host.in_indptr, host.in_indices,
                                      np.diff(host.indptr), damping=0.85,
                                      iters=iters)
    assert reference.max_rel_err(np.asarray(res.values), ref[-1]) < 1e-5


def test_bf16_storage_is_far_from_the_float64_iterates(session):
    from bench.calibrate import bf16_store

    host, _ = session
    args = (host.in_indptr, host.in_indices, np.diff(host.indptr))
    kw = dict(damping=0.85, iters=5)
    ref = reference.pagerank_iterates(*args, **kw)
    ctrl = reference.pagerank_iterates(*args, store=bf16_store, **kw)
    assert reference.max_rel_err(ctrl[-1], ref[-1]) > 1e-3


def test_max_rel_err_rejects_bad_shapes_and_nan():
    ref = np.ones(4)
    assert reference.max_rel_err(np.ones(3), ref) == float("inf")
    assert reference.max_rel_err(np.array([1, 1, np.nan, 1]), ref) \
        == float("inf")
    assert reference.max_rel_err(np.array([1, 1, 1.5, 1]), ref) == 0.5
