"""Readings that set the limits of an SSSP cell's ``correct``, on the chip,
in one process.

    python3 bench/calibrate_sssp.py --workload g500-s20-w.sssp \\
        --seeds 1 2 ... [--control-seeds 7 8 9] [--scale 21]

For each ``--seeds`` seed: the cell's set-up, the warm-up, and one timed
search, checked as a run checks it (the program's reading, with the
search's seconds).  For each ``--control-seeds`` seed: the control put in
the program's place, checked the same way: the same frontier Bellman-Ford
with weights and distances stored in bfloat16, the precision below the
program's float32.  ``--scale`` replaces the configuration's scale (to time
a search at a scale the cell was cut from).

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

from bench.calibrate import bf16_store  # noqa: E402
from bench.reference_sssp import bellman_ford  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--scale", type=int, default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from bench.compile_cache import use_compile_cache
    from bench.jobs import JobRecord
    from repro.core import recovery

    cell = harness.load_cell(args.workload)
    if cell.traffic["job"] != "graph500_sssp":
        raise SystemExit(f"{args.workload} is not an SSSP cell")
    if args.scale is not None:
        cell = cell._replace(config={**cell.config, "scale": args.scale})
    harness.check_chip(jax, cell)
    use_compile_cache()
    limits = cell.traffic["limits"]
    for role, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in seeds:
            t = time.perf_counter()
            s = harness.build(cell, seed)
            out = {"role": role, "seed": seed, "scale": cell.config["scale"]}
            if role == "program":
                s.jobs.warmup(s.graph, s.policy)
                t_job = time.perf_counter()
                job = s.jobs.run(s.graph, s.policy, 0)
                out["job_seconds"] = time.perf_counter() - t_job
            else:
                h, key = s.host, s.jobs.keys[0]
                job = JobRecord(0.0, bellman_ford(h.indptr, h.indices,
                                                  h.weights, key, bf16_store),
                                0, 0, key)
            job = job._replace(values=np.asarray(job.values))
            checks, _ = s.jobs.checks([job], limits)
            out.update(supersteps=job.supersteps, records=job.records,
                       component_edges=s.jobs.component_edges(job),
                       readings={k: v for k, (v, _) in checks.items()},
                       seconds=time.perf_counter() - t)
            print(json.dumps(out), flush=True)
            del s, job
            recovery._SEG_CACHE.clear()  # it holds each graph's device view
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
