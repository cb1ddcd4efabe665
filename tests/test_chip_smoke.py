"""``chip_smoke.py`` on the CPU: its phases at a small RMAT scale against
its numpy references, its refusal to run without a TPU, and the compile
cache helper it shares with ``bench/run.py``."""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_match_numpy_references(smoke):
    out = smoke.run_phases(10, tile_scale=9, sssp_scale=9, seed=1,
                           compiled=False, log=lambda s: None)
    assert out["b_memory"]["device_edge_total"] == 0
    assert out["a_memory"]["device_edge_total"] > 0
    assert out["tiles"] > 1
    assert out["d_seconds"] > 0


def test_sssp_check_refuses_a_wrong_distance(smoke):
    ref = np.array([0.0, 0.5, 1.5, np.inf])
    assert smoke._check_sssp("ok", ref.astype(np.float32), ref) == 0.0
    with pytest.raises(AssertionError, match="from Dijkstra"):
        smoke._check_sssp("off", np.array([0.0, 0.5, 1.6, np.inf]), ref)
    with pytest.raises(AssertionError, match="other vertices"):
        smoke._check_sssp("reach", np.array([0.0, 0.5, 1.5, 2.0]), ref)


def test_bfs_reference_on_a_path(smoke):
    # 0 -> 1 -> 2, vertex 3 isolated
    indptr = np.array([0, 1, 2, 2, 2])
    indices = np.array([1, 2], np.int32)
    dist = smoke.bfs_reference(indptr, indices, 0)
    assert dist.tolist() == [0, 1, 2, smoke.UNREACHED]


def test_pagerank_residual_is_zero_at_the_fixed_point(smoke):
    # a 2-cycle: the fixed point is uniform
    indptr = np.array([0, 1, 2])
    indices = np.array([1, 0], np.int32)
    assert smoke.pagerank_residual(indptr, indices, np.full(2, 0.5)) < 1e-12
    assert smoke.pagerank_residual(indptr, indices,
                                   np.array([0.6, 0.4])) > 0.1


def test_main_refuses_without_tpu(smoke, monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))

    def boom(*a, **k):
        raise AssertionError("phases must not run without a TPU")

    monkeypatch.setattr(smoke, "run_phases", boom)
    assert smoke.main([]) != 0


def test_compile_cache_is_fixed_inside_the_checkout(monkeypatch):
    import jax

    monkeypatch.syspath_prepend(str(ROOT))
    from bench.compile_cache import use_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    path = str(ROOT / ".jax_cache")
    assert use_compile_cache() == path
    assert ("jax_compilation_cache_dir", path) in calls


def test_compile_cache_keeps_every_program(monkeypatch):
    import jax

    monkeypatch.syspath_prepend(str(ROOT))
    from bench.compile_cache import use_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    use_compile_cache()
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in calls
    assert ("jax_persistent_cache_min_entry_size_bytes", 0) in calls
