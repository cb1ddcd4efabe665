"""The SSSP configuration and the two cells added with it, on the CPU at
RMAT scale 10: the weighted generator's graph, the discovery of the
configuration and of both cells, what decides an SSSP run's ``correct``
(sound runs pass; the timed path broken underneath, and the bfloat16
control, fail), the Dijkstra reference, and the relaxation's roofline
reader."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from bench import harness, reference_sssp, trace
from bench.calibrate import bf16_store
from bench.generators import graph500_kronecker as unweighted
from bench.generators import graph500_kronecker_weighted as weighted
from bench.jobs import JobRecord

CONFIG = {"graph_seed": 1, "scale": 8, "edgefactor": 16, "A": 0.57,
          "B": 0.19, "C": 0.19}
SEEDS = (3, 2**31 + 99)


@pytest.fixture(scope="module")
def graphs():
    return {seed: weighted.generate(seed, CONFIG) for seed in SEEDS}


def _edges(h):
    src = np.repeat(np.arange(h.n), np.diff(h.indptr))
    return src, h.indices, h.weights


def test_weighted_graph_is_the_unweighted_graph_with_weights(graphs):
    for seed, (h, unscrambled) in graphs.items():
        plain, plain_unscrambled = unweighted.generate(seed, CONFIG)
        assert np.array_equal(h.indptr, plain.indptr)
        assert np.array_equal(h.indices, plain.indices)
        assert np.array_equal(unscrambled, plain_unscrambled)
        assert h.weights.dtype == np.float32 and h.weights.shape == (h.m,)
        assert 0.0 <= h.weights.min() and h.weights.max() < 1.0
        assert h.in_weights is h.weights


def test_weights_are_symmetric(graphs):
    for h, _ in graphs.values():
        src, dst, w = _edges(h)
        fwd = dict(zip(zip(src.tolist(), dst.tolist()), w.tolist()))
        assert all(fwd[(b, a)] == x for (a, b), x in fwd.items())


def test_duplicates_keep_the_least_weight(graphs):
    """The weights from the tuples drawn again, in unscrambled labels."""
    h, unscrambled = graphs[SEEDS[0]]
    key = unweighted.seed_key(CONFIG["graph_seed"])
    src, dst = jax.device_get(weighted.kronecker_tuples(
        key, scale=CONFIG["scale"], edgefactor=CONFIG["edgefactor"],
        a=CONFIG["A"], b=CONFIG["B"], c=CONFIG["C"]))
    w = np.asarray(jax.random.uniform(
        jax.random.fold_in(key, weighted.WEIGHT_STREAM), src.shape,
        jnp.float32))
    least = {}
    for a, b, x in zip(src.tolist(), dst.tolist(), w.tolist()):
        if a != b:
            for pair in ((a, b), (b, a)):
                least[pair] = min(least.get(pair, np.inf), x)
    gs, gd, gw = _edges(h)
    got = dict(zip(zip(unscrambled[gs].tolist(), unscrambled[gd].tolist()),
                   gw.tolist()))
    assert got == least
    assert len(least) < 2 * len(src)  # duplicates were there to merge


def test_every_seed_serves_the_same_weights(graphs):
    views = []
    for h, unscrambled in graphs.values():
        src, dst, w = _edges(h)
        views.append(sorted(zip(unscrambled[src].tolist(),
                                unscrambled[dst].tolist(), w.tolist())))
    assert views[0] == views[1]
    assert not np.array_equal(graphs[SEEDS[0]][0].indices,
                              graphs[SEEDS[1]][0].indices)


def run(root, workload, *, trace=False, seed=2**31 + 99):
    return harness.run_cell(workload, seed=seed, seconds=0.05, trace=trace,
                            t_start=time.perf_counter(),
                            spec_path=root / "BENCHMARK.json",
                            require_chip=False, log=lambda s: None)


def _fake_device(real):
    """A device plane busy for the first half of the traced window."""
    def read(path):
        _, host = real(path)
        w = next(e for e in host if e.name == harness.WINDOW_SPAN)
        mid = (w.start_ns + w.end_ns) / 2
        return {"/device:TPU:0": [trace.Event("op", w.start_ns, mid)]}, host
    return read


def test_the_configuration_and_both_cells_are_found(small_root):
    cell = harness.load_cell("g500-s20-w.sssp",
                             small_root / "BENCHMARK.json")
    assert cell.config["name"] == "g500-s20-w"
    assert cell.traffic["job"] == "graph500_sssp"
    assert harness.generator(cell.config, small_root / "bench") is not None
    bfs = harness.load_cell("g500-s21.bfs", small_root / "BENCHMARK.json")
    assert bfs.config["name"] == "g500-s21" and bfs.traffic["job"] == "bfs"


@pytest.mark.parametrize("workload,per_layer", [
    ("g500-s20-w.sssp", {"relax_hbm_roofline.sssp", "records_per_edge.sssp",
                         "device_idle.sssp"}),
    ("g500-s21.bfs", {"device_idle.bfs", "records_per_edge.bfs"}),
])
def test_new_cells_run_timed_and_traced(small_root, monkeypatch, workload,
                                        per_layer):
    r = run(small_root, workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"bfs_teps", "setup_s"}
    monkeypatch.setattr(harness.trace_mod, "read_xplane",
                        _fake_device(trace.read_xplane))
    r = run(small_root, workload, trace=True)
    assert r["correct"] and set(r["metrics"]) == per_layer


def _patch(monkeypatch, alter):
    orig = repro.Graph.sssp

    def broken(self, *a, **kw):
        res = orig(self, *a, **kw)
        return res._replace(values=alter(self, res.values))

    monkeypatch.setattr(repro.Graph, "sssp", broken)


def _one_distance_altered(g, v):
    return v.at[int(jnp.argmax(jnp.where(jnp.isfinite(v), v, -1)))].multiply(
        1.001)


def _half_unreached(g, v):
    return v.at[g.n // 2:].set(jnp.inf)


def _bfloat16_control(g, v):
    h = g.host
    key = int(jnp.argmin(v))  # the search's source, at distance 0
    return jnp.asarray(reference_sssp.bellman_ford(
        h.indptr, h.indices, h.weights, key, bf16_store), jnp.float32)


@pytest.mark.parametrize("alter", [_one_distance_altered, _half_unreached,
                                   _bfloat16_control],
                         ids=lambda f: f.__name__)
def test_faults_and_the_control_fail(small_root, monkeypatch, alter):
    _patch(monkeypatch, alter)
    r = run(small_root, "g500-s20-w.sssp")
    assert not r["correct"] and r["failed"] >= 1
    checks = r["checks"]
    if alter is _half_unreached:
        assert checks["reach_wrong"]["value"] > 0
    else:
        assert checks["reach_wrong"]["value"] == 0
        assert (checks["dist_max_rel_err"]["value"]
                > checks["dist_max_rel_err"]["limit"])


def test_sound_run_reads_well_inside_the_limit(small_root):
    r = run(small_root, "g500-s20-w.sssp")
    c = r["checks"]
    assert c["reach_wrong"]["value"] == 0
    assert c["dist_max_rel_err"]["value"] < c["dist_max_rel_err"]["limit"]


def test_dijkstra_keeps_zero_weight_edges():
    # 0 -(0.0)-> 1 -(0.5)-> 2, vertex 3 unreached
    indptr = np.array([0, 1, 2, 2, 2])
    indices = np.array([1, 2], np.int32)
    weights = np.array([0.0, 0.5], np.float32)
    matrix = reference_sssp.weighted_csr(indptr, indices, weights)
    d = reference_sssp.distances(matrix, 0)
    assert d.tolist() == [0.0, 0.0, 0.5, np.inf]
    assert reference_sssp.component_edges(indptr, d) == 2
    assert reference_sssp.max_rel_err(np.array([0, 0, 0.5, np.inf]), d) == 0
    assert reference_sssp.max_rel_err(np.array([0, 1e-9, 0.5, np.inf]),
                                      d) == np.inf
    assert reference_sssp.reach_wrong(np.array([0, 0, 0.5, 1.0]), d) == 1


def test_relax_hbm_roofline_reads_least_bytes_over_busy_time():
    read = harness.metric_reader("relax_hbm_roofline.sssp",
                                 harness.BENCH / "metrics")
    job = JobRecord(1.0, None, 20, 0, 5)
    run_data = harness.RunData(
        jobs=[job, job], component_edges=[1000, 3000], n=100, m=4000,
        padded_edges=4096, peaks={"hbm_bytes_per_s": 1e6},
        trace={"busy_s": 0.5, "window_s": 1.0})
    least = (8 * 1000 + 8 * 100 + 8 * 3000 + 8 * 100) / 1e6
    assert read(run_data) == pytest.approx(100.0 * least / 0.5)
    assert read(run_data._replace(trace=None)) is None
    assert read(run_data._replace(component_edges=[None, None])) is None
