"""Benchmark driver: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--smoke]
[--json OUT.json]``

Emits ``bench,variant,metric,value`` CSV rows, then a claims-validation
summary comparing measured ratios against the direction/shape of the
paper's figures (exact magnitudes depend on the workload; the paper used
the 1.5B-edge Twitter graph on an SSD array, we use RMAT with matched skew
and count the same I/O events).

``--json OUT.json`` additionally writes the rows (and, for a full run, the
claim verdicts) as machine-readable JSON, so successive PRs can track the
perf trajectory (BENCH_PR2.json is the first recorded point).

``--smoke`` runs a seconds-fast CPU pass that exercises BOTH multicast
backends (chunked scan and the blocked Pallas tile kernel in interpret
mode) end-to-end through PageRank and multi-source BFS, asserting parity,
plus a mini frontier-density sweep asserting that the compact-scan path's
wall-clock actually tracks frontier density — the CI guard that the
blocked path and the compaction layer stay wired into the engine.  It
also re-runs PageRank under ``residency='host'`` (the true-SEM streamed
path), gating on bitwise host-vs-device parity, zero device-resident
edge bytes, and a non-zero measured ``host_bytes`` column.  It gates
the batched multi-source driver: the eager façade BFS (which routes
through it) must be bitwise the unbatched runs and its host-residency
sweep must amortize link bytes across the batch.  Finally it gates the
fault-tolerance layer: a mid-run kill resumed from its newest
checkpoint must be bitwise the uninterrupted run, checkpointing must
cost <5% wall-clock, and the lease queue's merged sweep must be
invariant to injected worker deaths.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import traceback

from .common import print_rows, row
from .compile_cache import use_compile_cache

BENCHES = [
    "bench_api",
    "bench_pagerank",
    "bench_coreness",
    "bench_diameter",
    "bench_bc",
    "bench_triangles",
    "bench_louvain",
    "bench_sem_vs_inmem",
    "bench_density",
    "bench_direction",
    "bench_tile_order",
    "bench_kernels",
    "bench_recovery",
    "bench_multisource",
]

# (bench, variant, metric, predicate, paper reference).  Magnitude targets
# are scaled to the bench workload (RMAT at laptop scale vs the paper's
# 1.5B-edge Twitter on an SSD array); EXPERIMENTS.md §Benchmarks discusses
# each gap.  Direction must always match the paper.
CLAIMS = [
    ("api", "pagerank", "facade_over_direct_x", lambda v: v < 1.02,
     "Graph facade adds <2% overhead over direct traverse() loops"),
    ("api", "facade", "parity_ok", lambda v: v == 1.0,
     "Graph facade is bitwise-equal (values+IOStats) to direct loops"),
    ("api", "analyze", "analyzed_over_plain_x", lambda v: v < 1.05,
     "analyze=True pre-flight is a one-time trace: warmed analyzed runs "
     "within 5% of plain runs (analysis cached, zero per-superstep cost)"),
    ("pagerank", "push_over_pull", "read_reduction_x", lambda v: v > 1.2,
     "Fig.2: push reads less than pull (paper: 1.8x)"),
    ("pagerank", "push_over_pull", "request_reduction_x", lambda v: v > 1.3,
     "Fig.2: push issues fewer I/O requests (paper: ~5x)"),
    ("pagerank", "push_over_pull", "io_time_speedup_x", lambda v: v > 1.2,
     "Fig.2: push faster on the paper's SSD-bound runtime (paper: 2.2x)"),
    ("coreness", "prune_over_unopt", "superstep_reduction_x", lambda v: v > 8.0,
     "Fig.3: k-pruning collapses supersteps (paper: ~10x alone)"),
    ("coreness", "hybrid_over_prune", "read_reduction_x", lambda v: v > 1.5,
     "Fig.3: hybrid messaging cuts bytes further (paper: 2.3x)"),
    ("diameter", "multi_over_uni", "superstep_reduction_x", lambda v: v > 4.0,
     "Fig.5: multi-source BFS slashes global barriers"),
    ("diameter", "multi_over_uni", "read_reduction_x", lambda v: v > 2.0,
     "Fig.5: multi-source reuses fetched chunks"),
    ("bc", "multi_over_uni", "read_reduction_x", lambda v: v > 2.0,
     "Fig.6: multi-source BC moves less data (paper: 4x @32 sources)"),
    ("bc", "fused", "shared_chunk_fetches", lambda v: v > 0,
     "Fig.6a: fused phases share fetches (cache-hit ratio rises)"),
    ("triangles", "hash", "speedup_comparisons_x", lambda v: v > 8.0,
     "Fig.7: full optimization ladder (paper: ~2 orders of magnitude)"),
    ("triangles", "restarted", "speedup_comparisons_x", lambda v: v > 2.0,
     "Fig.7: restarted binary search beats scan intersection"),
    ("louvain", "graphyti", "bytes_written_MB", lambda v: v == 0.0,
     "Fig.8: Graphyti path writes no edge data"),
    ("sem_vs_inmem", "sem", "fraction_of_inmem", lambda v: v > 0.6,
     "Abstract: SEM ~80% of in-memory performance"),
    ("sem_vs_inmem", "sem", "memory_reduction_x", lambda v: v > 4.0,
     "Abstract: memory cut ~(m/n)x (paper: 20-100x on Twitter)"),
    ("sem_vs_inmem", "sem_host", "fraction_of_inmem", lambda v: v >= 0.5,
     "Abstract (true SEM, CPU link proxy): host-streamed edges >=50% of "
     "in-memory speed (paper: ~80% from SSD)"),
    ("sem_vs_inmem", "sem_host", "host_link_bytes", lambda v: v > 0,
     "Residency: the host run's edge bytes crossed the host link "
     "(measured, not modeled)"),
    ("sem_vs_inmem", "sem_host", "device_edge_bytes", lambda v: v == 0.0,
     "Residency: a host session keeps ZERO edge bytes device-resident"),
    ("density", "compact", "monotone_ok", lambda v: v >= 1.0,
     "P1 paid in time: compact-scan wall-clock tracks frontier density"),
    ("density", "flat", "flat_ratio", lambda v: v < 1.6,
     "The full in-memory pass is density-blind (flat wall-clock)"),
    ("density", "compact", "sparse_speedup_x", lambda v: v > 4.0,
     "Compact scan at 0.1% frontier is far cheaper than at 100%"),
    ("density", "compact_vs_flat", "sparsest_speedup_x", lambda v: v > 3.0,
     "At the sparse tail, compacted SEM beats the in-memory full pass"),
    ("direction", "rmat_adaptive", "vs_best_static_x", lambda v: v <= 1.15,
     "Beamer α/β: adaptive BFS at/below the best static direction (RMAT)"),
    ("direction", "path_adaptive", "vs_best_static_x", lambda v: v <= 1.15,
     "Beamer β gate pins adaptive to push on a high-diameter path graph"),
    ("direction", "rmat", "modes_agree", lambda v: v == 1.0,
     "Direction changes wall-clock/bytes, never levels or messages (RMAT)"),
    ("direction", "path", "modes_agree", lambda v: v == 1.0,
     "Direction changes wall-clock/bytes, never levels or messages (path)"),
    ("tile_order", "rmat_hilbert", "x_fetch_reduction_x", lambda v: v >= 4 / 3,
     "Hilbert tile order cuts x-block DMA re-fetches >=25% on skewed RMAT"),
    ("tile_order", "rmat_morton", "x_fetch_reduction_x", lambda v: v > 1.1,
     "Morton (dst-fastest) order also beats destination-sorted streaming"),
    ("tile_order", "uniform_hilbert", "x_fetch_reduction_x",
     lambda v: v >= 1.0,
     "Curve order never fetches MORE x blocks than 'dest' (uniform graph)"),
    ("tile_order", "rmat", "orders_agree", lambda v: v == 1.0,
     "Tile order changes the schedule, never values or record/tile bytes"),
    ("tile_order", "uniform", "orders_agree", lambda v: v == 1.0,
     "Order-invariance holds on the uniform workload too"),
    ("spmv_kernel", "local_0.05", "tile_skip_ratio", lambda v: v > 0.5,
     "Kernel: frontier block skipping elides most tile DMAs"),
    ("decode_attn_kernel", "window_256_vs_full", "fetch_reduction_x",
     lambda v: v > 4.0,
     "Kernel: window decode skips out-of-window KV blocks (P1 on LM)"),
    ("recovery", "pagerank", "checkpoint_sync_frac", lambda v: v < 0.05,
     "Fault tolerance: snapshotting every 8 supersteps costs <5% wall-clock "
     "(measured synchronous checkpoint seconds / checkpointed runtime)"),
    ("recovery", "pagerank", "kill_resume_parity_ok", lambda v: v == 1.0,
     "Fault tolerance: killed-and-resumed run is bitwise the uninterrupted "
     "run (values + full IOStats ledger)"),
    ("recovery", "pagerank", "recover_speedup_x", lambda v: v > 1.5,
     "Fault tolerance: resuming the newest checkpoint beats a from-scratch "
     "rerun (crash at 2/3 of the run)"),
    ("recovery", "queue", "death_invariance_ok", lambda v: v == 1.0,
     "Lease queue: the merged multi-source sweep is bitwise-invariant to "
     "injected worker deaths"),
    ("recovery", "chaos", "chaos_bitwise_parity", lambda v: v == 1.0,
     "Durable queue: real OS workers, one SIGKILL'd + one stalled "
     "mid-sweep, supervisor restarts — merged result bitwise the "
     "crash-free single-process run"),
    ("recovery", "snapshot", "delta_shrink_x", lambda v: v >= 2.0,
     "Delta snapshots of slowly-changing BFS state store >=2x fewer "
     "bytes than full snapshots, with bitwise resume-from-delta"),
    ("recovery", "snapshot", "delta_resume_parity_ok", lambda v: v == 1.0,
     "Resuming a delta snapshot chain after a mid-run kill is bitwise "
     "the uninterrupted run"),
    ("recovery", "snapshot", "stage_bound_ok", lambda v: v == 1.0,
     "Streaming sharded saves never stage more than one "
     "max_shard_bytes budget on host at once"),
    ("multisource", "batched", "parity_ok", lambda v: v == 1.0,
     "Serving: the Q=8 batched run is bitwise-equal to its 8 solo runs "
     "(values + per-query supersteps, both residencies)"),
    ("multisource", "host_q8", "bytes_per_query_reduction_x",
     lambda v: v >= 4.0,
     "Serving: batched Q=8 BFS moves >=4x fewer host-link bytes per query "
     "than solo runs (one streamed tile serves the whole batch)"),
    ("multisource", "device_q8", "records_per_query_reduction_x",
     lambda v: v > 2.0,
     "Serving: the chunk ledger shows the same per-query amortization on "
     "the device-resident path"),
]


def smoke(json_out: str | None = None) -> int:
    """Seconds-fast blocked-backend + compaction exercise (see docstring),
    plus a mini direction sweep: push/pull/adaptive BFS must agree on
    levels AND messages (noise-free correctness gate), with the per-mode
    runtime/byte rows recorded for the perf-trajectory artifact.

    Everything runs through the ``repro.Graph`` façade, gated on parity
    with the legacy entry points: per backend, values AND IOStats of the
    façade call must be bitwise-equal to ``pagerank_push``/``bfs_multi``
    on a freshly built device graph — the CI guard that the façade, the
    program runner, and the session view cache stay wired to the same
    engine the shims use."""
    import warnings

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.algs import bfs_multi, pagerank_push
    from repro.core import ExecutionPolicy, device_graph
    from repro.graph.generators import path_graph, rmat

    from . import bench_density, bench_direction, bench_tile_order
    from .common import timeit

    t0 = time.time()
    g = rmat(7, edge_factor=8, seed=2)
    session = repro.Graph(g, chunk_size=256, bd=32, bs=32)
    sg = device_graph(g, chunk_size=256, blocked=True, bd=32, bs=32)
    rows = []
    results = {}
    facade_ok = True
    for backend in ("scan", "compact", "blocked", "blocked_compact"):
        pol = ExecutionPolicy(backend=backend, chunk_cap=2)
        fn = jax.jit(lambda p=pol: session.pagerank(tol=1e-4, policy=p))
        res, t = timeit(fn, repeats=1)
        results[backend] = np.asarray(res.values)
        rows += [
            row("smoke", f"push_{backend}", "runtime_s", t),
            row("smoke", f"push_{backend}", "fetches_skipped",
                int(res.iostats.chunks_skipped)),
        ]
        src = jnp.asarray([0, 5, 17, 99], jnp.int32)
        bpol = ExecutionPolicy(backend=backend, switch_fraction=None)
        bres, tb = timeit(
            jax.jit(lambda p=bpol: session.bfs(src, policy=p)), repeats=1
        )
        results[f"bfs_{backend}"] = np.asarray(bres.values)
        rows.append(row("smoke", f"bfs4_{backend}", "runtime_s", tb))
        # façade-vs-legacy parity gate (values AND the full IOStats ledger).
        # Both sides jitted: jit-vs-eager float rounding is not the façade's
        # doing, and jit-vs-jit of identical programs IS bitwise.
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            r_l, io_l, it_l = jax.jit(
                lambda p=pol: pagerank_push(sg, tol=1e-4, policy=p))()
            d_l, bio_l, _ = jax.jit(
                lambda p=bpol: bfs_multi(sg, src, policy=p))()
        facade_ok &= bool((np.asarray(r_l) == results[backend]).all())
        facade_ok &= bool((np.asarray(d_l) == results[f"bfs_{backend}"]).all())
        facade_ok &= all(int(a) == int(b) for a, b in zip(io_l, res.iostats))
        facade_ok &= all(int(a) == int(b) for a, b in zip(bio_l, bres.iostats))
        facade_ok &= int(it_l) == int(res.supersteps)
    err = max(
        float(np.max(np.abs(results["scan"] - results[b])))
        for b in ("compact", "blocked", "blocked_compact")
    )
    bfs_ok = all(
        bool((results["bfs_scan"] == results[f"bfs_{b}"]).all())
        for b in ("compact", "blocked", "blocked_compact")
    )
    rows.append(row("smoke", "backends", "pagerank_maxerr", err))
    rows.append(row("smoke", "facade", "parity_ok", 1.0 if facade_ok else 0.0))

    # host-residency gate: the same PageRank/BFS must be bitwise-equal
    # (values + every order-invariant IOStats field) when the edge store
    # stays in host RAM and streams per superstep.  Compared against an
    # EAGER device run — the host driver mirrors the eager BSP loop's
    # codegen, and eager-vs-jit float rounding is XLA's, not the engine's.
    # ``host_bytes`` (the measured link odometer) prints as its own column
    # and must be non-zero: a zero would mean nothing actually streamed.
    sem_host_ok = True
    for backend in ("scan", "blocked_compact"):
        pol = ExecutionPolicy(backend=backend, chunk_cap=2)
        hpol = pol.with_(residency="host")
        dres = repro.Graph(g, chunk_size=256, bd=32, bs=32).pagerank(
            tol=1e-4, policy=pol)
        hsession = repro.Graph(g, chunk_size=256, bd=32, bs=32)
        hres, th = timeit(
            lambda: hsession.pagerank(tol=1e-4, policy=hpol), repeats=1)
        sem_host_ok &= bool(
            (np.asarray(hres.values) == np.asarray(dres.values)).all())
        sem_host_ok &= all(
            int(a) == int(b)
            for f, a, b in zip(dres.iostats._fields, dres.iostats,
                               hres.iostats) if f != "host_bytes")
        sem_host_ok &= int(hres.iostats.host_bytes) > 0
        mr = hsession.memory_report(hpol)
        sem_host_ok &= mr["device_edge_total"] == 0
        rows += [
            row("smoke", f"host_{backend}", "runtime_s", th),
            row("smoke", f"host_{backend}", "host_bytes",
                int(hres.iostats.host_bytes)),
            row("smoke", f"host_{backend}", "device_edge_bytes",
                mr["device_edge_total"]),
        ]

    # mini frontier-density sweep: compact wall-clock must track density.
    gd = rmat(10, edge_factor=8, seed=42)
    sgd = device_graph(gd, chunk_size=64)
    drows, times = bench_density.sweep(
        sgd, [1.0, 0.1, 0.01, 0.001], repeats=5, lanes=2, label="smoke_density"
    )
    rows += drows + bench_density.summarize(times, label="smoke_density")
    # Gate on the dense-vs-sparsest ratio, which is orders of magnitude and
    # robust to scheduler noise; pairwise monotonicity of sub-millisecond
    # points is recorded as a metric row but would flake on shared CI
    # runners, so it does not gate.
    dens_speedup = times["compact"][0] / times["compact"][-1]
    dens_ok = dens_speedup >= 2.0

    # mini direction sweep: per-superstep push/pull/adaptive dispatch must
    # never change levels or messages; runtimes ride along as artifacts
    # (wall-clock ratios at this scale are scheduler noise, so they are
    # recorded but do not gate).
    gp = path_graph(512)
    gd8 = rmat(8, edge_factor=8, seed=5, symmetrize=True)
    sgd8 = device_graph(gd8, chunk_size=64)
    drows2, ratios = bench_direction.sweep(
        [("rmat", sgd8, int(jnp.argmax(sgd8.out_degree))),
         ("path", device_graph(gp, chunk_size=64), 0)],
        repeats=2, label="smoke_direction",
    )
    rows += drows2
    dir_ok = all(agree == 1.0 for _, agree in ratios.values())

    # mini tile-order sweep (skewed RMAT): every order must agree bitwise
    # with 'dest' (values + order-invariant IOStats), and the hilbert
    # schedule must not fetch MORE x blocks than destination-sorted
    # streaming — the CI guard that the curve layouts, the accumulate-on-
    # flush kernel contract, and the x-fetch accounting stay wired.
    trows, tsum = bench_tile_order.sweep(
        [("rmat", gd8)], bd=32, bs=32, chunk_size=256, repeats=1,
        densities=(1.0, 0.25), label="smoke_tile_order",
    )
    rows += trows
    order_ok = (
        tsum["rmat"]["agree"] == 1.0
        and tsum["rmat"]["hilbert"] <= tsum["rmat"]["dest"]
    )

    # batched multi-source gate: the eager façade bfs routes through the
    # batched driver — values must be bitwise the jitted (unbatched) runs
    # above, with the Q stamp and per-query supersteps present; and under
    # residency='host' the batched sweep must move at most half the
    # host-link bytes of its solo runs summed (the amortization claim at
    # smoke scale; the >=4x-at-Q=8 gate runs in bench_multisource).
    src4 = jnp.asarray([0, 5, 17, 99], jnp.int32)
    mspol = ExecutionPolicy(backend="scan", switch_fraction=None)
    ms = session.bfs(src4, policy=mspol)
    ms_ok = bool((np.asarray(ms.values) == results["bfs_scan"]).all())
    ms_ok &= int(ms.iostats.queries) == 4 and ms.query_supersteps is not None
    mssess = repro.Graph(g, chunk_size=256, bd=32, bs=32)
    hb = mssess.bfs(src4, policy=mspol.with_(residency="host"))
    ms_ok &= bool((np.asarray(hb.values) == results["bfs_scan"]).all())
    solo_bytes = sum(
        int(mssess.bfs(int(s),
                       policy=mspol.with_(residency="host")).iostats.host_bytes)
        for s in np.asarray(src4))
    amort_x = solo_bytes / max(int(hb.iostats.host_bytes), 1)
    amort_ok = amort_x >= 2.0
    rows += [
        row("smoke", "multisource", "parity_ok", 1.0 if ms_ok else 0.0),
        row("smoke", "multisource", "host_amortization_x", amort_x),
    ]

    # fault-tolerance gate: a PageRank run killed mid-flight and resumed
    # from its newest snapshot must be bitwise the uninterrupted run,
    # snapshots must cost <5% wall-clock (measured at a scale where
    # supersteps do real work, so fixed costs amortize), and the lease
    # queue's merged BC sweep must be invariant to injected worker deaths.
    from . import bench_recovery

    rrows, rsum = bench_recovery.measure(label="smoke_recovery")
    rows += rrows
    recovery_ok = (rsum["parity_ok"] == 1.0 and rsum["queue_ok"] == 1.0
                   and rsum["sync_frac"] < 0.05
                   and rsum["chaos_ok"] == 1.0
                   and rsum["delta_ratio"] >= 2.0
                   and rsum["delta_parity_ok"] == 1.0
                   and rsum["stage_ok"] == 1.0)

    print_rows(rows)
    ok = (err < 1e-5 and bfs_ok and dens_ok and dir_ok and facade_ok
          and order_ok and sem_host_ok and recovery_ok and ms_ok
          and amort_ok)
    host_col = {r["variant"]: int(r["value"]) for r in rows
                if r["metric"] == "host_bytes"}
    print(f"# smoke {'PASS' if ok else 'FAIL'} in {time.time() - t0:.1f}s "
          f"(pagerank maxerr {err:.2g}, bfs equal {bfs_ok}, "
          f"compact sparse speedup {dens_speedup:.1f}x, "
          f"direction modes agree {dir_ok}, "
          f"facade parity {facade_ok}, "
          f"host residency parity {sem_host_ok} "
          f"[host_bytes {host_col}], "
          f"tile orders agree {order_ok} "
          f"[hilbert {tsum['rmat']['hilbert']} <= dest "
          f"{tsum['rmat']['dest']} x-fetches], "
          f"kill-resume parity {rsum['parity_ok'] == 1.0}, "
          f"checkpoint sync overhead {100 * rsum['sync_frac']:.2f}% "
          f"[wall ratio {rsum['overhead_x']:.3f}x], "
          f"queue death invariance {rsum['queue_ok'] == 1.0}, "
          f"chaos bitwise parity {rsum['chaos_ok'] == 1.0} "
          f"[{rsum['chaos_restarts']} restarts, "
          f"{rsum['chaos_stale']} stale rejections, "
          f"{rsum['chaos_vs_clean_x']:.2f}x vs clean], "
          f"delta snapshots {rsum['delta_ratio']:.1f}x smaller "
          f"[resume parity {rsum['delta_parity_ok'] == 1.0}, "
          f"staging bound {rsum['stage_ok'] == 1.0}], "
          f"batched multisource parity {ms_ok}, "
          f"batched host amortization {amort_x:.1f}x)")
    if json_out:
        _write_json(json_out, rows, ok=ok, mode="smoke")
    return 0 if ok else 1


def _write_json(path: str, rows: list, *, ok: bool, mode: str,
                claims: list | None = None) -> None:
    """Machine-readable result dump: the perf-trajectory record."""
    payload = {"mode": mode, "ok": ok, "rows": rows}
    if claims is not None:
        payload["claims"] = claims
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {path}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="larger workloads")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--smoke", action="store_true",
        help="seconds-fast CPU pass exercising the blocked backend",
    )
    ap.add_argument(
        "--json", default=None, metavar="OUT.json",
        help="also write rows (and claim verdicts) as JSON",
    )
    args = ap.parse_args()
    print(f"# compile cache: {use_compile_cache()}", flush=True)
    if args.smoke:
        if args.only or args.full:
            print("# --smoke ignores --only/--full", flush=True)
        return smoke(json_out=args.json)

    rows = []
    failures = []
    for name in BENCHES:
        if args.only and args.only not in name:
            continue
        mod = importlib.import_module(f".{name}", __package__)
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            r = mod.run(quick=not args.full)
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED\n{traceback.format_exc()}", flush=True)
            continue
        rows += r
        print_rows(r)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)

    # ---- claims validation ----
    index = {(r["bench"], r["variant"], r["metric"]): r["value"] for r in rows}
    print("\n# === paper-claim validation ===")
    n_ok = 0
    n_checked = 0
    verdicts = []
    for bench, variant, metric, pred, ref in CLAIMS:
        key = (bench, variant, metric)
        if key not in index:
            if args.only:
                continue
            print(f"MISSING  {ref}  [{bench}/{variant}/{metric}]")
            verdicts.append({"claim": ref, "status": "missing"})
            continue
        v = index[key]
        ok = pred(v)
        n_checked += 1
        n_ok += ok
        print(f"{'PASS' if ok else 'FAIL'}  {ref}  -> measured {v:.3g}")
        verdicts.append(
            {"claim": ref, "status": "pass" if ok else "fail", "measured": v}
        )
    print(f"\n# claims: {n_ok}/{n_checked} pass; bench modules failed: {failures or 'none'}")
    all_ok = n_ok == n_checked and not failures
    if args.json:
        _write_json(args.json, rows, ok=all_ok, mode="full", claims=verdicts)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
