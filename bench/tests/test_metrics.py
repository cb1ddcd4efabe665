"""Metric arithmetic: TEPS over components, edges per iteration, the
roofline byte count, and the per-layer readers."""
import numpy as np
import pytest

from bench import harness, reference
from bench.jobs import JobRecord
from repro.graph.csr import Graph as HostGraph

METRICS = harness.BENCH / "metrics"
U = reference.UNREACHED


@pytest.fixture
def path4():
    """Path 0-1-2 plus the edge 3-4 and the isolated vertex 5."""
    indptr = np.array([0, 1, 3, 4, 5, 6, 6], np.int64)
    indices = np.array([1, 0, 2, 1, 4, 3], np.int32)
    return HostGraph(n=6, indptr=indptr, indices=indices, in_indptr=indptr,
                     in_indices=indices)


def make_jobs(traffic, host, unscrambled=None):
    kind = harness.load_module(harness.BENCH / "jobs", traffic["job"])
    if unscrambled is None:
        unscrambled = np.arange(host.n, dtype=np.int32)
    return kind.Jobs(traffic, host, unscrambled)


def bfs_gen(host, unscrambled=None):
    traffic = {"job": "bfs", "params": {"max_keys": 2, "key_seed": 3}}
    return make_jobs(traffic, host, unscrambled)


def test_teps_counts_undirected_edges_of_each_searched_component(path4):
    gen = bfs_gen(path4)
    first = JobRecord(1.0, np.array([0, 1, 2, U, U, U], np.int32), 3, 0, 0)
    second = JobRecord(1.0, np.array([U, U, U, 0, 1, U], np.int32), 2, 0, 3)
    assert gen.component_edges(first) == 4
    assert gen.component_edges(second) == 2
    assert gen.work(first) + gen.work(second) == 3.0  # 2 + 1 undirected


def test_pagerank_work_is_edges_times_iterations(path4):
    traffic = {"job": "pagerank", "params": {"mode": "pull", "damping": 0.85,
                                             "tol": 0.0, "max_iters": 5}}
    gen = make_jobs(traffic, path4)
    assert gen.work(JobRecord(1.0, None, 5, 0, None)) == 6 * 5
    assert gen.component_edges(JobRecord(1.0, None, 5, 0, None)) is None


def test_pagerank_traffic_needs_fixed_iterations(path4):
    with pytest.raises(ValueError, match="tol = 0"):
        make_jobs({"job": "pagerank", "params": {"tol": 1e-3}}, path4)
    with pytest.raises(ValueError, match="sssp.py"):
        make_jobs({"job": "sssp", "params": {}}, path4)


def test_bfs_keys_have_an_edge_and_follow_the_seed(path4):
    a, b = bfs_gen(path4), bfs_gen(path4)
    assert a.keys == b.keys
    assert np.all(np.diff(path4.indptr)[a.keys] >= 1)
    # Relabelled by a permutation, the graph's keys are the same vertices.
    perm = np.array([4, 2, 0, 5, 1, 3])  # old label -> new label
    inv = np.argsort(perm)
    n = path4.n
    row = np.repeat(np.arange(n), np.diff(path4.indptr))
    new = np.zeros((n, n), bool)
    new[perm[row], perm[path4.indices]] = True
    indptr = np.concatenate([[0], np.cumsum(new.sum(1))]).astype(np.int64)
    indices = np.nonzero(new)[1].astype(np.int32)
    relabelled = HostGraph(n=n, indptr=indptr, indices=indices,
                           in_indptr=indptr, in_indices=indices)
    c = bfs_gen(relabelled, unscrambled=inv.astype(np.int32))
    assert [int(perm[k]) for k in a.keys] == c.keys


def test_roofline_byte_count():
    roof = harness.metric_reader("superstep_hbm_roofline.pr", METRICS)
    n, m = 2**21, 63_541_044
    assert roof.__globals__["iteration_bytes"](n, m) == 4 * m + 12 * n
    run = harness.RunData(
        jobs=[JobRecord(1.0, None, 2, 0, None)], component_edges=[None], n=n,
        m=m, padded_edges=0, peaks={"hbm_bytes_per_s": 819e9},
        trace={"busy_s": 4.0, "window_s": 5.0})
    want = 100 * 2 * (4 * m + 12 * n) / 819e9 / 4.0
    assert roof(run) == pytest.approx(want)
    assert roof(run._replace(trace=None)) is None


def test_device_idle_and_records_per_edge_readers():
    idle = harness.metric_reader("device_idle.pr", METRICS)
    rpe = harness.metric_reader("records_per_edge.bfs", METRICS)
    jobs_ = [JobRecord(1.0, None, 7, 4096 * 30, 1),
             JobRecord(1.0, None, 7, 4096 * 10, 2)]
    run = harness.RunData(jobs_, [4096 * 10, 4096 * 10], 10, 20,
                          padded_edges=4096 * 2, peaks={},
                          trace={"busy_s": 1.0, "window_s": 4.0})
    assert idle(run) == pytest.approx(75.0)
    assert rpe(run) == pytest.approx(2.0)
    # A search that could wrap int32 records gives no reading.
    assert rpe(run._replace(padded_edges=2**30)) is None
    assert rpe(run._replace(component_edges=[None, None])) is None
    assert idle(run._replace(trace=None)) is None
