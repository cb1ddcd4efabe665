"""Fault-tolerance cost: what does surviving a crash actually buy/cost?

Two numbers matter for the paper's long-running-SEM-job story:

  * **checkpoint overhead** — what snapshotting every ``every_k``
    supersteps costs a run.  Both plain and checkpointed runs ride the
    same trace-cached segmented driver (eager ``run_program`` dispatches
    through it since the recovery work landed), so the costs specific to
    checkpointing are segment-boundary re-dispatch plus the O(n)
    device->host state copy, with serialization async off the hot loop.
    Gated on the ``CheckpointSpec(telemetry=...)`` odometer — the
    measured synchronous seconds the checkpoint layer adds, as a
    fraction of wall-clock (<5%); the differential plain-vs-checkpointed
    ratio rides along as a recorded artifact (it is jitter-dominated at
    bench scale and does not gate).
  * **time to recover** — wall-clock of the resumed run after a mid-job
    kill, vs re-running from scratch: the later the crash, the larger the
    win (the resume replays at most ``every_k - 1`` supersteps).

Also recorded: the lease-queue sweep's death-invariance (merged BC with
injected worker deaths is bitwise the no-deaths merge) — the queue's
whole point, measured end to end; the multi-process chaos sweep (real OS
workers over the durable queue, one SIGKILL'd mid-sweep plus one stall,
supervisor restarts — gate ``chaos_bitwise_parity`` against a crash-free
single-process run, record the chaos-vs-clean wall ratio); and the
streaming/delta snapshot economics (delta snapshots of a slowly-changing
BFS state on a path graph, gated >=2x smaller than full snapshots with
resume-from-delta bitwise parity; sharded-save peak staging gated <= one
``max_shard_bytes`` budget).
"""
from __future__ import annotations

import shutil
import tempfile
import time as _time
from pathlib import Path

import jax.numpy as jnp
import numpy as np

import repro
from repro.core import (
    CheckpointSpec,
    DeviceFailure,
    DurableWorkQueue,
    ExecutionPolicy,
    FailurePlan,
    ManualClock,
    WorkQueue,
    run_program,
    run_supervised,
    run_workers,
    shard_sources,
)
from repro.algs.bfs import BFSProgram
from repro.algs.pagerank import PageRankPullProgram
from repro.graph.generators import path_graph, rmat

from .common import row, timeit

# ---- multi-process chaos sweep fixtures (module-level: spawn workers
# pickle the work fn by reference and re-import this module) ----
_CHAOS_SCALE = 6
_chaos_cache: dict = {}


def _chaos_bfs(payload):
    """One durable-queue task: batched BFS from a 2-source group; result =
    flat [values..., iostats...] float64 vector so the canonical additive
    merge covers values AND the order-invariant I/O ledger."""
    s = _chaos_cache.get("s")
    if s is None:
        s = repro.Graph(
            rmat(_CHAOS_SCALE, edge_factor=6, seed=3, symmetrize=True),
            chunk_size=64, bd=32, bs=32)
        _chaos_cache["s"] = s
    r = s.bfs(np.asarray(payload, np.int32),
              policy=ExecutionPolicy(backend="scan"))
    vals = np.asarray(r.values, np.float64).reshape(-1)
    io = np.asarray([float(v) for v in r.iostats], np.float64)
    return np.concatenate([vals, io])


def measure(*, scale: int = 14, every_k: int = 8, repeats: int = 3,
            label: str = "recovery"):
    """Returns (rows, summary).  ``summary``: overhead_x, parity_ok (1.0
    iff the killed-and-resumed run is bitwise the uninterrupted run),
    queue_ok (death-invariant merge), recover_s, scratch_s."""
    g = rmat(scale, edge_factor=16, seed=2, symmetrize=True)
    session = repro.Graph(g, chunk_size=256, bd=32, bs=32)
    sem = session.device()
    prog = PageRankPullProgram(tol=1e-6)
    work = Path(tempfile.mkdtemp(prefix="bench_recovery_"))
    rows = []
    try:
        # -- checkpoint overhead --
        # The gating statistic is the telemetry odometer: the seconds the
        # checkpoint layer spends *synchronously* on the hot path
        # (device->host snapshot, async handoff/join, the blocking final
        # write), as a fraction of checkpointed wall-clock.  A
        # differential plain-vs-checkpointed comparison cannot resolve a
        # few-percent cost under multi-tenant CPU jitter (the same run
        # drifts +-10% trial to trial); the odometer measures the cost
        # directly.  The wall-clock ratio is still recorded (paired +
        # interleaved, median of per-pair ratios so slow drift cancels)
        # as a non-gating artifact.
        tele = {"sync_s": 0.0, "saves": 0}

        def plain_run():
            return run_program(sem, prog, max_supersteps=60)

        def ckpt_run():
            d = work / "overhead"
            shutil.rmtree(d, ignore_errors=True)
            return run_program(sem, prog, max_supersteps=60,
                               checkpoint=CheckpointSpec(
                                   d, every_k=every_k, telemetry=tele))

        base = plain_run()  # warmup (populates the driver's trace cache)
        ck = ckpt_run()
        tele["sync_s"], tele["saves"] = 0.0, 0
        t_plain = t_ck = float("inf")
        ck_sum = 0.0
        ratios = []
        for _ in range(repeats):
            base, tp = timeit(plain_run, repeats=1, warmup=0)
            ck, tc = timeit(ckpt_run, repeats=1, warmup=0)
            t_plain, t_ck = min(t_plain, tp), min(t_ck, tc)
            ck_sum += tc
            ratios.append(tc / tp)
        overhead = sorted(ratios)[len(ratios) // 2]
        sync_frac = tele["sync_s"] / ck_sum
        total = int(base.supersteps)
        parity = float(
            np.array_equal(np.asarray(base.values), np.asarray(ck.values))
            and int(base.supersteps) == int(ck.supersteps)
            and all(int(a) == int(b) for a, b in zip(base.iostats, ck.iostats))
        )

        # -- kill mid-run, resume; recovery time vs from-scratch --
        kill_at = max(1, (total * 2) // 3)
        spec = CheckpointSpec(work / "kill", every_k=every_k)

        def killed_then_resumed():
            shutil.rmtree(spec.directory, ignore_errors=True)
            return run_supervised(sem, prog, max_supersteps=60,
                                  checkpoint=spec,
                                  plan=FailurePlan({kill_at: "crash"}))
        (res, rep), _ = timeit(killed_then_resumed, repeats=1, warmup=0)
        parity *= float(
            np.array_equal(np.asarray(base.values), np.asarray(res.values))
            and all(int(a) == int(b)
                    for a, b in zip(base.iostats, res.iostats)))
        # the recovery alone: resume the surviving checkpoint directory
        _, t_recover = timeit(
            lambda: run_program(sem, prog, max_supersteps=60,
                                checkpoint=spec, resume=True),
            repeats=1, warmup=0)
        # NB: the finished run's final snapshot makes this resume nearly
        # instant; the honest recover number is crash-time replay, so
        # measure from the pre-crash snapshot instead.
        shutil.rmtree(spec.directory, ignore_errors=True)
        try:
            run_program(sem, prog, max_supersteps=60, checkpoint=spec,
                        _plan=FailurePlan({kill_at: "crash"}))
        except DeviceFailure:
            pass  # the injected crash
        _, t_recover = timeit(
            lambda: run_program(sem, prog, max_supersteps=60,
                                checkpoint=spec, resume=True),
            repeats=1, warmup=0)

        # -- queue death-invariance (BC sweep; small graph, the queue
        # machinery not the SpMV is under test) --
        qsession = repro.Graph(rmat(9, edge_factor=8, seed=2,
                                    symmetrize=True),
                               chunk_size=256, bd=32, bs=32)
        pol = ExecutionPolicy(backend="scan")
        shards = shard_sources(np.arange(8), 2)
        tpl = np.zeros(qsession.n, np.float32)

        def bc_shard(src):
            return np.asarray(
                qsession.betweenness(jnp.asarray(src, jnp.int32),
                                     policy=pol).values)

        def sweep(deaths):
            q = WorkQueue(shards, result_template=tpl, clock=ManualClock(),
                          lease_timeout=5.0)
            run_workers(q, bc_shard, deaths=deaths)
            return q.merge(lambda a, b: a + b)
        queue_ok = float(np.array_equal(sweep([]), sweep([(0, 1), (2, 1)])))

        # -- multi-process chaos sweep: real OS workers, one SIGKILL'd
        # mid-sweep, one stalled past its lease; the supervisor restarts
        # and the merged result must be bitwise the crash-free
        # single-process run's --
        ctasks = shard_sources(np.arange(8), 2)
        ctpl = np.zeros((2 ** _CHAOS_SCALE) * 2 + 10, np.float64)
        clean_q = DurableWorkQueue(work / "chaos_clean", ctasks,
                                   lease_timeout=10.0, result_template=ctpl)
        t0c = _time.perf_counter()
        clean_rep = run_workers(clean_q, _chaos_bfs, processes=1,
                                timeout=300.0)
        t_chaos_clean = _time.perf_counter() - t0c
        chaos_q = DurableWorkQueue(work / "chaos", ctasks,
                                   lease_timeout=1.5, max_attempts=4,
                                   result_template=ctpl)
        t0c = _time.perf_counter()
        chaos_rep = run_workers(chaos_q, _chaos_bfs, processes=3,
                                faults={(1, 1): "sigkill", (2, 1): 2.0},
                                timeout=300.0)
        t_chaos = _time.perf_counter() - t0c
        chaos_ok = float(
            clean_rep.finished and chaos_rep.finished
            and chaos_rep.kills >= 1 and chaos_rep.dead_letters == []
            and np.array_equal(clean_q.merge(lambda a, b: a + b),
                               chaos_q.merge(lambda a, b: a + b)))
        chaos_vs_clean = t_chaos / max(t_chaos_clean, 1e-9)

        # -- streaming + delta snapshot economics: BFS on a path graph is
        # the canonical slowly-changing state (one wavefront vertex moves
        # per superstep; the settled distance prefix never changes), so
        # delta snapshots should store a small fraction of the full
        # state.  Peak staging of the sharded writer gates <= one shard.
        pg = repro.Graph(path_graph(4096), chunk_size=256, bd=32, bs=32)
        psem = pg.device()
        pseeds = jnp.asarray([0], jnp.int32)
        budget = 2048
        base_p = run_program(psem, BFSProgram(), seeds=pseeds,
                             max_supersteps=40)

        def snap_run(name, delta):
            tel = {}
            d = work / name
            shutil.rmtree(d, ignore_errors=True)
            run_program(psem, BFSProgram(), seeds=pseeds, max_supersteps=40,
                        checkpoint=CheckpointSpec(
                            d, every_k=1, keep=8, async_save=False,
                            max_shard_bytes=budget, delta=delta,
                            telemetry=tel))
            return tel

        tel_full = snap_run("snap_full", False)
        tel_delta = snap_run("snap_delta", True)
        delta_ratio = tel_full["bytes_written"] / max(
            tel_delta["bytes_written"], 1)
        stage_ok = float(0 < tel_full["stage_peak_bytes"] <= budget)
        # resume-from-delta: kill mid-run, resume the delta chain, bitwise
        dres, drep = run_supervised(
            psem, BFSProgram(), seeds=pseeds, max_supersteps=40,
            checkpoint=CheckpointSpec(work / "snap_kill", every_k=4,
                                      max_shard_bytes=budget, delta=True),
            plan=FailurePlan({25: "crash"}))
        delta_parity = float(
            drep.restarts == 1
            and np.array_equal(np.asarray(base_p.values),
                               np.asarray(dres.values))
            and all(int(a) == int(b)
                    for a, b in zip(base_p.iostats, dres.iostats)))

        rows += [
            row(label, "pagerank", "supersteps", total),
            row(label, "pagerank", "plain_runtime_s", t_plain),
            row(label, "pagerank", "checkpointed_runtime_s", t_ck),
            row(label, "pagerank", "checkpoint_overhead_x", overhead),
            row(label, "pagerank", "checkpoint_sync_frac", sync_frac),
            row(label, "pagerank", "checkpoint_saves_per_run",
                tele["saves"] / repeats),
            row(label, "pagerank", "kill_resume_parity_ok", parity),
            row(label, "pagerank", "time_to_recover_s", t_recover),
            row(label, "pagerank", "scratch_rerun_s", t_plain),
            row(label, "pagerank", "recover_speedup_x",
                t_plain / max(t_recover, 1e-9)),
            row(label, "queue", "death_invariance_ok", queue_ok),
            row(label, "chaos", "chaos_bitwise_parity", chaos_ok),
            row(label, "chaos", "chaos_vs_clean_x", chaos_vs_clean),
            row(label, "chaos", "chaos_restarts", chaos_rep.restarts),
            row(label, "chaos", "chaos_stale_rejections",
                chaos_rep.stale_rejections),
            row(label, "snapshot", "delta_shrink_x", delta_ratio),
            row(label, "snapshot", "delta_resume_parity_ok", delta_parity),
            row(label, "snapshot", "full_snapshot_bytes",
                tel_full["bytes_written"]),
            row(label, "snapshot", "delta_snapshot_bytes",
                tel_delta["bytes_written"]),
            row(label, "snapshot", "stage_peak_bytes",
                tel_full["stage_peak_bytes"]),
            row(label, "snapshot", "stage_bound_ok", stage_ok),
        ]
        summary = {"overhead_x": overhead, "sync_frac": sync_frac,
                   "parity_ok": parity, "queue_ok": queue_ok,
                   "recover_s": t_recover, "scratch_s": t_plain,
                   "chaos_ok": chaos_ok, "chaos_vs_clean_x": chaos_vs_clean,
                   "chaos_restarts": chaos_rep.restarts,
                   "chaos_stale": chaos_rep.stale_rejections,
                   "delta_ratio": delta_ratio,
                   "delta_parity_ok": delta_parity, "stage_ok": stage_ok}
        return rows, summary
    finally:
        shutil.rmtree(work, ignore_errors=True)


def run(quick: bool = True):
    rows, _ = measure(scale=14 if quick else 15,
                      repeats=3 if quick else 5)
    return rows
