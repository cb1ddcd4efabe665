"""Readings that set the limits of ``correct``, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 ... \\
        [--control-seeds 7 8 9]

For each ``--seeds`` seed: the cell's set-up and one timed job, checked as a
run checks it (the program's reading).  For each ``--control-seeds`` seed:
the control put in the program's place, checked the same way:

* ``pagerank``: the reference computed with ranks and contributions stored
  in bfloat16, the precision below the program's float32;
* ``bfs``: the program with ``residency='device'``, which breaks the
  semi-external configuration's guarantee that no edge byte is on the
  device (only for cells whose configuration states that guarantee).

Prints one JSON line per reading.  Benchmark runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def bf16_store(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def control_checks(cell, session) -> tuple[int, dict]:
    """``(supersteps, readings)`` of the control on the session's graph."""
    from bench import reference

    jobs = session.jobs
    if cell.traffic["job"] == "pagerank":
        iters = jobs.params["max_iters"]
        ref = jobs.reference_iterates(iters)
        ctrl = jobs.reference_iterates(iters, store=bf16_store)
        return iters, {
            "rank_max_rel_err": reference.max_rel_err(ctrl[-1], ref[-1])}
    pol = session.policy.with_(residency="device")
    job = jobs.call(session.graph, pol, jobs.keys[0], {})
    checks, _ = session.jobs.checks(
        [job._replace(values=np.asarray(job.values))],
        cell.traffic["limits"])
    out = {k: v for k, (v, _) in checks.items()}
    out["device_edge_total"] = session.graph.memory_report(pol)[
        "device_edge_total"]
    return job.supersteps, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.compile_cache import use_compile_cache
    from repro.core import recovery

    cell = harness.load_cell(args.workload)
    harness.check_chip(jax, cell)
    use_compile_cache()
    for role, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in seeds:
            t = time.perf_counter()
            s = harness.build(cell, seed)
            if role == "program":
                job = s.jobs.run(s.graph, s.policy, 0)
                steps = job.supersteps
                checks, _ = s.jobs.checks(
                    [job._replace(values=np.asarray(job.values))],
                    cell.traffic["limits"])
                report = s.graph.memory_report(s.policy)
                for key, limit in cell.config.get("guarantees", {}).items():
                    checks[key] = (report[key], limit)
                readings = {k: v for k, (v, _) in checks.items()}
            else:
                steps, readings = control_checks(cell, s)
            print(json.dumps({"role": role, "seed": seed,
                              "supersteps": steps,
                              "readings": readings,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
            del s
            recovery._SEG_CACHE.clear()  # it holds each graph's device view
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
