"""A configuration, a traffic mix, a graph generator, a job kind and a
per-layer metric, each added as a file of its own and named in
BENCHMARK.json, run with no edit to any file that was there; a name with
no file, or a configuration that states another graph than its generator
makes, is refused."""
import json
import time

import pytest

from bench import harness, trace

NEW_METRIC = '''
def read(run):
    return float(sum(job.supersteps for job in run.jobs))
'''


@pytest.fixture
def grown_root(small_root):
    bench = small_root / "bench"
    config = json.loads((bench / "configs/g500-s21.json").read_text())
    config["scale"] = 9
    (bench / "configs/tiny.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic/pr.json").read_text())
    traffic["params"]["max_iters"] = 3
    (bench / "traffic/pr3.json").write_text(json.dumps(traffic))
    (bench / "metrics/supersteps.py").write_text(NEW_METRIC)
    spec_path = small_root / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "tiny.pr3", "config": "tiny",
                              "traffic": "pr3", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "supersteps.pr3", "unit": "steps",
                              "better": "lower", "source": "program_counter",
                              "layer": "device", "moves": "pr_edges_per_s",
                              "workloads": ["tiny.pr3"]})
    spec_path.write_text(json.dumps(spec))
    return small_root


def _fake_device(real):
    """The CPU has no device plane: give the trace one that is busy for the
    first half of the window."""
    def read(path):
        _, host = real(path)
        w = next(e for e in host if e.name == harness.WINDOW_SPAN)
        mid = (w.start_ns + w.end_ns) / 2
        return {"/device:TPU:0": [trace.Event("op", w.start_ns, mid)]}, host
    return read


def _run(root, workload, tr):
    return harness.run_cell(workload, seed=4, seconds=0.05, trace=tr,
                            t_start=time.perf_counter(),
                            spec_path=root / "BENCHMARK.json",
                            require_chip=False, log=lambda s: None)


def test_added_files_run(grown_root, monkeypatch):
    r = _run(grown_root, "tiny.pr3", False)
    assert r["correct"] and set(r["metrics"]) == {"pr_edges_per_s",
                                                  "setup_s"}
    monkeypatch.setattr(harness.trace_mod, "read_xplane",
                        _fake_device(trace.read_xplane))
    r = _run(grown_root, "tiny.pr3", True)
    assert r["correct"]
    # Traced jobs run one iteration (the traffic's trace parameters).
    assert r["metrics"]["supersteps.pr3"] == {"value": 1.0, "unit": "steps"}
    assert "device_idle.pr" not in r["metrics"]
    assert r["device"]["window_s"] > 0
    assert r["device"]["busy_s"] == pytest.approx(r["device"]["window_s"] / 2)
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_traced_cells_report_their_per_layer_metrics(small_root, monkeypatch):
    monkeypatch.setattr(harness.trace_mod, "read_xplane",
                        _fake_device(trace.read_xplane))
    pr = _run(small_root, "g500-s21.pr", True)
    assert set(pr["metrics"]) == {"device_idle.pr",
                                  "superstep_hbm_roofline.pr"}
    assert pr["metrics"]["device_idle.pr"]["value"] == pytest.approx(50.0)
    bfs = _run(small_root, "g500-s21-sem.bfs", True)
    assert set(bfs["metrics"]) == {"device_idle.bfs", "records_per_edge.bfs"}
    assert bfs["correct"] and pr["correct"]


def test_metric_reader_falls_back_to_the_stem(small_root):
    metrics = small_root / "bench" / "metrics"
    assert harness.metric_reader("device_idle.anything", metrics) is not None
    with pytest.raises(ValueError, match="no_such_metric.py"):
        harness.metric_reader("no_such_metric.pr", metrics)


RING = '''
import numpy as np
from repro.graph.csr import Graph

PRODUCES = {"symmetric": True, "self_loops": "dropped",
            "duplicates": "dropped"}


def generate(seed, config):
    n = config["vertices"]
    v = np.arange(n)
    nbrs = np.sort(np.stack([(v - 1) % n, (v + 1) % n], 1), 1)
    indptr = np.arange(0, 2 * n + 1, 2, dtype=np.int64)
    indices = nbrs.reshape(-1).astype(np.int32)
    return (Graph(n=n, indptr=indptr, indices=indices, in_indptr=indptr,
                  in_indices=indices), v.astype(np.int32))
'''

FROM_ZERO = '''
from bench.jobs.bfs import Jobs as BFSJobs


class Jobs(BFSJobs):
    """Searches from vertex 0 only."""

    def run(self, g, policy, index, *, traced=False):
        return self.call(g, policy, 0, {})
'''


@pytest.fixture
def ring_root(small_root):
    """A ring graph (a high-diameter generator) under a new job kind."""
    bench = small_root / "bench"
    (bench / "generators/ring.py").write_text(RING)
    (bench / "jobs/from_zero.py").write_text(FROM_ZERO)
    config = {"name": "ring", "generator": "ring", "vertices": 64,
              "symmetric": True, "self_loops": "dropped",
              "duplicates": "dropped", "chunk_size": 16,
              "policy": {"switch_fraction": None, "residency": "host"},
              "guarantees": {"device_edge_total": 0}}
    (bench / "configs/ring.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic/bfs.json").read_text())
    traffic["job"] = "from_zero"
    (bench / "traffic/zero.json").write_text(json.dumps(traffic))
    spec_path = small_root / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["configs"].append({"name": "ring", "source": "test",
                            "file": "bench/configs/ring.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "ring.zero", "config": "ring",
                              "traffic": "zero", "chips": 1, "why": "test"})
    spec_path.write_text(json.dumps(spec))
    return small_root


def test_added_generator_and_job_kind_run(ring_root):
    r = _run(ring_root, "ring.zero", False)
    assert r["correct"] and r["checks"]["levels_wrong"]["value"] == 0
    assert set(r["metrics"]) == {"bfs_teps", "setup_s"}


@pytest.mark.parametrize("key,value", [("symmetric", False),
                                       ("self_loops", "kept"),
                                       ("duplicates", None)])
def test_a_config_that_states_another_graph_is_refused(ring_root, key,
                                                       value):
    path = ring_root / "bench/configs/ring.json"
    config = json.loads(path.read_text())
    if value is None:
        del config[key]
    else:
        config[key] = value
    path.write_text(json.dumps(config))
    with pytest.raises(ValueError, match=f"states {key}="):
        _run(ring_root, "ring.zero", False)


@pytest.mark.parametrize("file,key", [("configs/ring.json", "generator"),
                                      ("traffic/zero.json", "job")])
def test_an_unknown_generator_or_job_kind_is_refused(ring_root, file, key):
    path = ring_root / "bench" / file
    data = json.loads(path.read_text())
    data[key] = "no_such_name"
    path.write_text(json.dumps(data))
    with pytest.raises(ValueError, match="no_such_name.py"):
        _run(ring_root, "ring.zero", False)
