"""The chip benchmark of the repository: one cell of ``BENCHMARK.json``
per run of ``python3 bench/run.py``.  See ``bench/harness.py``."""
