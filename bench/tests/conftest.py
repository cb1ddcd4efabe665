"""Fixtures of the benchmark's CPU tests: a copy of the benchmark at a size
that a test run holds (RMAT scale 10 instead of the cells' scale)."""
import json
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
SCALE = 10


def make_root(dst: Path, scale: int = SCALE) -> Path:
    """``dst`` with ``BENCHMARK.json`` and the benchmark's configurations,
    traffic, generators, job kinds and metrics copied from the repository,
    every configuration cut to ``scale``."""
    (dst / "bench").mkdir(parents=True)
    shutil.copy(BENCH.parent / "BENCHMARK.json", dst / "BENCHMARK.json")
    for sub in ("configs", "traffic", "generators", "jobs", "metrics"):
        shutil.copytree(BENCH / sub, dst / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for path in (dst / "bench" / "configs").glob("*.json"):
        config = json.loads(path.read_text())
        config["scale"] = scale
        path.write_text(json.dumps(config))
    return dst


@pytest.fixture
def small_root(tmp_path) -> Path:
    return make_root(tmp_path / "root")
