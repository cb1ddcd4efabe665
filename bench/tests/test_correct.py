"""What decides ``correct``: sound runs pass, the controls and the faults
of the timed path fail.  Runs drive the whole harness on the CPU at RMAT
scale 10 (the look for a chip skipped), with the timed path broken
underneath where a test says so."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from bench import harness, reference

RUN = harness.BENCH / "run.py"
UNREACHED = reference.UNREACHED


def run(root, workload, *, trace=False, seed=2**31 + 99):
    return harness.run_cell(workload, seed=seed, seconds=0.05, trace=trace,
                            t_start=time.perf_counter(),
                            spec_path=root / "BENCHMARK.json",
                            require_chip=False, log=lambda s: None)


@pytest.mark.parametrize("workload", ["g500-s21.pr", "g500-s21-sem.bfs"])
def test_sound_run_is_correct(small_root, workload):
    r = run(small_root, workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())
    assert set(r["metrics"]) == {"setup_s", {"g500-s21.pr": "pr_edges_per_s",
                                             "g500-s21-sem.bfs": "bfs_teps"}
                                 [workload]}


def _patch(monkeypatch, method, alter):
    orig = getattr(repro.Graph, method)

    def broken(self, *a, **kw):
        res = orig(self, *a, **kw)
        return res._replace(values=alter(self, res.values))

    monkeypatch.setattr(repro.Graph, method, broken)


def _unchanged_ranks(g, v):
    return jnp.full_like(v, 1.0 / g.n)


def _half_ranks_stale(g, v):
    return v.at[g.n // 2:].set(1.0 / g.n)


def _one_rank_altered(g, v):
    return v.at[int(jnp.argmax(v))].multiply(1.01)


def _unchanged_levels(g, v):
    return jnp.where(v == 0, 0, UNREACHED).astype(v.dtype)


def _half_levels_lost(g, v):
    return v.at[g.n // 2:].set(UNREACHED)


def _one_level_altered(g, v):
    return v.at[int(jnp.argmax(jnp.where(v == UNREACHED, -1, v)))].add(1)


@pytest.mark.parametrize("workload,method,alter", [
    ("g500-s21.pr", "pagerank", _unchanged_ranks),
    ("g500-s21.pr", "pagerank", _half_ranks_stale),
    ("g500-s21.pr", "pagerank", _one_rank_altered),
    ("g500-s21-sem.bfs", "bfs", _unchanged_levels),
    ("g500-s21-sem.bfs", "bfs", _half_levels_lost),
    ("g500-s21-sem.bfs", "bfs", _one_level_altered),
], ids=lambda x: getattr(x, "__name__", x))
def test_faults_of_the_timed_path_fail(small_root, monkeypatch, workload,
                                       method, alter):
    _patch(monkeypatch, method, alter)
    r = run(small_root, workload)
    assert not r["correct"] and r["failed"] >= 1


def test_pagerank_control_fails(small_root, monkeypatch):
    """The reference with bfloat16 storage, put in the program's place."""
    from bench.calibrate import bf16_store

    traffic = json.loads((small_root / "bench/traffic/pr.json").read_text())
    limit = traffic["limits"]["rank_max_rel_err"]
    real = reference.pagerank_iterates

    def control(g, v):
        h = g.host
        return jnp.asarray(real(h.in_indptr, h.in_indices,
                                np.diff(h.indptr), damping=0.85,
                                iters=traffic["params"]["max_iters"],
                                store=bf16_store)[-1], jnp.float32)

    _patch(monkeypatch, "pagerank", control)
    r = run(small_root, "g500-s21.pr")
    assert r["checks"]["rank_max_rel_err"]["value"] > limit
    assert not r["correct"]


def test_sem_control_fails(small_root):
    """The program with its device-resident path switched on breaks the
    semi-external guarantee, though every level is right."""
    path = small_root / "bench/configs/g500-s21-sem.json"
    config = json.loads(path.read_text())
    config["policy"]["residency"] = "device"
    path.write_text(json.dumps(config))
    r = run(small_root, "g500-s21-sem.bfs")
    assert r["checks"]["levels_wrong"]["value"] == 0
    assert r["checks"]["device_edge_total"]["value"] > 0
    assert not r["correct"]


def _cli(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s21.pr",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_tpu_prints_no_result():
    p = _cli(harness.ROOT)
    assert p.returncode == 2 and p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_cli_in_a_bare_benchmark_directory_fails(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
