"""Fault tolerance: checkpoint durability, resume-exact BSP, stream retry,
and the lease-based work queue.

The contract under test is the strongest one the library can make: a run
killed at ANY superstep and resumed is *bitwise-equal* — values, superstep
count, and the full IOStats ledger (``host_bytes`` and ``retries``
included) — to a run that was never interrupted, on every backend and both
residencies; and a multi-source sweep whose workers die mid-lease merges
to exactly the same bits as one where nobody died.
"""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.checkpoint import (
    CheckpointCorruptionError,
    CheckpointManager,
    latest_step,
    load_extra,
    restore_checkpoint,
    save_checkpoint,
)
from repro.core import (
    CheckpointMismatchError,
    CheckpointSpec,
    DeviceFailure,
    ExecutionPolicy,
    FailurePlan,
    ManualClock,
    QueueMismatchError,
    StreamFailure,
    WorkQueue,
    inject_stream_faults,
    run_program,
    run_supervised,
    run_workers,
    shard_sources,
)
from repro.algs.bfs import BFSProgram
from repro.algs.pagerank import PageRankPullProgram, PageRankPushProgram
from repro.graph.generators import rmat

pytestmark = pytest.mark.kernel

BACKENDS = ("scan", "compact", "blocked", "blocked_compact")


@pytest.fixture(scope="module")
def host():
    # Small enough that kill-at-every-superstep sweeps stay fast, chunked
    # small enough that host streaming ships several batches per superstep.
    return rmat(6, edge_factor=6, seed=3, symmetrize=True)


def session(host):
    return repro.Graph(host, chunk_size=64, bd=32, bs=32)


def views(host):
    s = session(host)
    return s.device(), s.host_view()


def assert_identical(a, b, *, skip=()):
    """Full bitwise equality: values, supersteps, EVERY IOStats field."""
    assert np.array_equal(np.asarray(a.values), np.asarray(b.values))
    assert int(a.supersteps) == int(b.supersteps)
    for name, x, y in zip(a.iostats._fields, a.iostats, b.iostats):
        if name in skip:
            continue
        assert int(x) == int(y), f"IOStats.{name}: {int(x)} != {int(y)}"


# ------------------------------------------------------------ store
class TestStoreDurability:
    def test_tmp_partial_and_stray_entries_ignored(self, tmp_path):
        tree = {"a": jnp.arange(5), "b": jnp.ones(3)}
        save_checkpoint(tmp_path, 4, tree)
        # a crashed save leaves a .tmp; stray dirs happen to real operators
        (tmp_path / "step_00000099.tmp").mkdir()
        (tmp_path / "step_junk").mkdir()
        (tmp_path / "step_").mkdir()
        assert latest_step(tmp_path) == 4
        restored, step = restore_checkpoint(
            tmp_path, {"a": jnp.zeros(5, jnp.int32), "b": jnp.zeros(3)})
        assert step == 4
        assert np.array_equal(np.asarray(restored["a"]), np.arange(5))
        # retention gc must also step over the strays
        mgr = CheckpointManager(tmp_path, keep=1)
        mgr.save(7, tree)
        assert latest_step(tmp_path) == 7

    def test_corrupt_shard_is_an_error(self, tmp_path):
        save_checkpoint(tmp_path, 1, {"a": jnp.arange(4), "b": jnp.ones(2)})
        shard = tmp_path / "step_00000001" / "proc0.npz"
        np.savez(shard, a0=np.arange(4))  # one leaf missing
        with pytest.raises(CheckpointCorruptionError, match="manifest"):
            restore_checkpoint(
                tmp_path, {"a": jnp.zeros(4, jnp.int32), "b": jnp.zeros(2)})

    def test_extra_metadata_round_trip(self, tmp_path):
        save_checkpoint(tmp_path, 2, {"a": jnp.zeros(1)},
                        extra={"graph": "abc", "superstep": 2})
        assert load_extra(tmp_path, 2) == {"graph": "abc", "superstep": 2}
        assert load_extra(tmp_path, 3) is None

    def test_as_numpy_preserves_dtypes(self, tmp_path):
        save_checkpoint(tmp_path, 1, {"r": np.arange(3, dtype=np.float64)})
        tree, _ = restore_checkpoint(
            tmp_path, {"r": np.zeros(3, np.float64)}, as_numpy=True)
        assert tree["r"].dtype == np.float64


# ------------------------------------------------------------ resume-exact
class TestResumeExact:
    def test_kill_at_every_superstep(self, host, tmp_path):
        """The headline contract, exhaustively on one backend: crash at
        superstep k for EVERY k, resume, and the result is bitwise the
        uninterrupted run's — wherever k falls relative to every_k."""
        sem, _ = views(host)
        prog = PageRankPullProgram(tol=1e-4)
        base = run_program(sem, prog, max_supersteps=30)
        total = int(base.supersteps)
        assert total > 5
        for k in range(total):
            d = tmp_path / f"kill_{k}"
            res, rep = run_supervised(
                sem, prog, max_supersteps=30,
                checkpoint=CheckpointSpec(d, every_k=3),
                plan=FailurePlan({k: "crash"}))
            assert rep.restarts == 1
            assert_identical(base, res)

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("residency", ("device", "host"))
    def test_backends_and_residencies(self, host, tmp_path, backend,
                                      residency):
        """Spot kills on every backend x residency: PageRank killed twice
        (once off-cadence), BFS killed once.  host_bytes and retries are
        compared too — same-residency runs must agree on the whole
        ledger."""
        s = session(host)
        pol = ExecutionPolicy(backend=backend, residency=residency)
        prog = PageRankPullProgram(tol=1e-4)
        sem = s._sem(pol, prog)  # the view the façade would run this on
        base = run_program(sem, prog, pol, max_supersteps=25)
        res, rep = run_supervised(
            sem, prog, pol, max_supersteps=25,
            checkpoint=CheckpointSpec(tmp_path / "pr", every_k=2),
            plan=FailurePlan({3: "crash", 7: "crash"}))
        assert rep.restarts == 2
        assert_identical(base, res)

        bfs = BFSProgram()
        seeds = jnp.asarray([0], jnp.int32)
        sem = s._sem(pol, bfs)
        base_b = run_program(sem, bfs, pol, seeds=seeds)
        res_b, _ = run_supervised(
            sem, bfs, pol, seeds=seeds,
            checkpoint=CheckpointSpec(tmp_path / "bfs", every_k=2),
            plan=FailurePlan({2: "crash"}))
        assert_identical(base_b, res_b)

    @pytest.mark.parametrize("residency", ("device", "host"))
    def test_betweenness_phase_checkpoints(self, host, tmp_path, residency):
        """A kill in the backward sweep resumes there; the forward phase
        replays from its final snapshot (its own `fwd/` subtree)."""
        s_base, s_ck = session(host), session(host)
        pol = ExecutionPolicy(backend="scan", residency=residency)
        src = jnp.arange(3)
        base = s_base.betweenness(src, policy=pol)
        spec = CheckpointSpec(tmp_path / "bc", every_k=2)
        ck = s_ck.betweenness(src, policy=pol, checkpoint=spec)
        assert_identical(base, ck)
        assert (tmp_path / "bc" / "fwd").is_dir()
        assert (tmp_path / "bc" / "bwd").is_dir()
        again = s_ck.betweenness(src, policy=pol, checkpoint=spec,
                                 resume=True)
        assert_identical(base, again)

    def test_checkpoint_overhead_free_parity(self, host, tmp_path):
        """checkpoint= with no crash must not perturb anything, on every
        backend (the segmented driver replaces the single while_loop)."""
        s = session(host)
        prog = PageRankPushProgram(tol=1e-4)
        for backend in BACKENDS:
            pol = ExecutionPolicy(backend=backend)
            sem = s._sem(pol, prog)
            base = run_program(sem, prog, pol, max_supersteps=25)
            res = run_program(
                sem, prog, pol, max_supersteps=25,
                checkpoint=CheckpointSpec(tmp_path / backend, every_k=4))
            assert_identical(base, res)

    def test_finished_run_resumes_instantly(self, host, tmp_path):
        sem, _ = views(host)
        prog = PageRankPullProgram(tol=1e-4)
        spec = CheckpointSpec(tmp_path, every_k=4)
        first = run_program(sem, prog, max_supersteps=25, checkpoint=spec)
        again = run_program(sem, prog, max_supersteps=25, checkpoint=spec,
                            resume=True)
        assert_identical(first, again)

    def test_fingerprint_mismatch_raises(self, host, tmp_path):
        sem, _ = views(host)
        spec = CheckpointSpec(tmp_path, every_k=2)
        run_program(sem, PageRankPullProgram(tol=1e-3), max_supersteps=10,
                    checkpoint=spec)
        with pytest.raises(CheckpointMismatchError, match="program"):
            run_program(sem, PageRankPullProgram(tol=1e-5),
                        max_supersteps=10, checkpoint=spec, resume=True)
        with pytest.raises(CheckpointMismatchError, match="program"):
            run_program(sem, BFSProgram(), seeds=jnp.asarray([0], jnp.int32),
                        checkpoint=spec, resume=True)
        with pytest.raises(CheckpointMismatchError, match="seeds"):
            # same program class/config, different seeds
            spec2 = CheckpointSpec(tmp_path / "s", every_k=2)
            run_program(sem, BFSProgram(), seeds=jnp.asarray([0], jnp.int32),
                        checkpoint=spec2)
            run_program(sem, BFSProgram(), seeds=jnp.asarray([1], jnp.int32),
                        checkpoint=spec2, resume=True)

    def test_checkpoint_rejects_tracers(self, host, tmp_path):
        import jax

        sem, _ = views(host)
        with pytest.raises(ValueError, match="eagerly"):
            jax.jit(lambda: run_program(
                sem, PageRankPullProgram(), max_supersteps=5,
                checkpoint=CheckpointSpec(tmp_path)))()


# ------------------------------------------------------------ stream retry
class TestStreamRetry:
    def test_transient_faults_absorbed_and_counted(self, host):
        _, hv = views(host)
        prog = PageRankPullProgram(tol=1e-4)
        pol = ExecutionPolicy(residency="host", stream_backoff_s=0.0)
        base = run_program(hv, prog, pol, max_supersteps=10)
        assert int(base.iostats.retries) == 0

        calls = [0]

        def flaky():  # attempts 2 and 5 fail once each
            calls[0] += 1
            if calls[0] in (2, 5):
                raise OSError("transient link drop")

        with inject_stream_faults(flaky):
            res = run_program(hv, prog, pol, max_supersteps=10)
        assert int(res.iostats.retries) == 2
        # values and every other ledger field are untouched by the retries
        assert_identical(base, res, skip=("retries",))

    def test_exhaustion_raises_stream_failure(self, host):
        _, hv = views(host)
        pol = ExecutionPolicy(residency="host", stream_retries=2,
                              stream_backoff_s=0.0)

        def down():
            raise OSError("link down")

        with inject_stream_faults(down):
            with pytest.raises(StreamFailure, match="after 3 attempts"):
                run_program(hv, PageRankPullProgram(), pol, max_supersteps=5)

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            ExecutionPolicy(stream_retries=-1)
        with pytest.raises(ValueError):
            ExecutionPolicy(stream_backoff_s=-0.1)


# ------------------------------------------------------------ work queue
def _work(src):
    out = np.zeros(16)
    for s in np.asarray(src).reshape(-1):
        out[int(s) % 16] += 0.1 * float(s) + 1.0
    return out


class TestWorkQueue:
    def make(self, **kw):
        kw.setdefault("result_template", np.zeros(16))
        kw.setdefault("clock", ManualClock())
        kw.setdefault("lease_timeout", 5.0)
        return WorkQueue(shard_sources(np.arange(23), 5), **kw)

    def test_lease_expiry_reissues(self):
        q = self.make()
        l1 = q.lease()
        assert (l1.tid, l1.attempt) == (0, 1)
        q._clock.advance(6.0)
        l2 = q.lease()  # the expired task comes back before task 1
        assert (l2.tid, l2.attempt) == (0, 2)
        # the dead worker's late result is a stale token: rejected
        assert not q.complete(l1, _work(l1.payload))
        assert not q.completed[0]
        assert q.complete(l2, _work(l2.payload))

    def test_dead_letter_after_max_attempts(self):
        q = self.make(max_attempts=2)
        run_workers(q, _work, deaths=[(0, 1), (0, 2)])
        assert q.dead_letters == [0]
        assert q.finished
        assert q.completed[1:].all()

    def test_merge_is_death_invariant(self):
        clean = run_workers(self.make(), _work)
        m0 = clean.merge(lambda a, b: a + b)
        # worker deaths mid-lease change the merged result by exactly nothing
        dead = run_workers(self.make(), _work,
                           deaths=[(1, 1), (3, 1), (3, 2), (4, 1)])
        m1 = dead.merge(lambda a, b: a + b)
        assert np.array_equal(m0, m1)
        assert dead.attempts[3] == 3

    def test_merge_order_is_canonical(self):
        """Completion order must not leak into the fold (float addition is
        not associative): complete tasks backwards, merge equal anyway."""
        fwd = run_workers(self.make(), _work)
        q = self.make()
        leases = [q.lease() for _ in range(q.num_tasks)]
        for l in reversed(leases):
            assert q.complete(l, _work(l.payload))
        assert np.array_equal(fwd.merge(lambda a, b: a + b),
                              q.merge(lambda a, b: a + b))

    def test_checkpoint_resume_mid_sweep(self, tmp_path):
        full = run_workers(self.make(), _work).merge(lambda a, b: a + b)
        q = self.make()
        for _ in range(2):
            l = q.lease()
            q.complete(l, _work(l.payload))
        q.checkpoint(tmp_path)
        # process dies here; a new queue over the same shards resumes
        q2 = self.make()
        assert q2.resume(tmp_path)
        assert int(q2.completed.sum()) == 2
        run_workers(q2, _work)
        assert np.array_equal(full, q2.merge(lambda a, b: a + b))

    def test_resume_rejects_different_sharding(self, tmp_path):
        q = self.make()
        l = q.lease()
        q.complete(l, _work(l.payload))
        q.checkpoint(tmp_path)
        other = WorkQueue(shard_sources(np.arange(23), 4),
                          result_template=np.zeros(16), clock=ManualClock())
        with pytest.raises(QueueMismatchError):
            other.resume(tmp_path)

    def test_resume_empty_dir_is_fresh_start(self, tmp_path):
        assert not self.make().resume(tmp_path / "nothing_here")

    def test_bc_sweep_through_queue(self, host, tmp_path):
        """End to end: exact-ish BC sharded over the queue; injected
        worker death changes the merged centrality by exactly nothing."""
        s = session(host)
        pol = ExecutionPolicy(backend="scan")
        shards = shard_sources(np.arange(6), 2)
        tpl = np.zeros(s.n, np.float32)

        def bc_shard(src):
            r = s.betweenness(jnp.asarray(src, jnp.int32), policy=pol)
            return np.asarray(r.values)

        def sweep(deaths):
            q = WorkQueue(shards, result_template=tpl, clock=ManualClock(),
                          lease_timeout=5.0)
            run_workers(q, bc_shard, deaths=deaths,
                        checkpoint_dir=tmp_path / f"q{len(deaths)}")
            return q.merge(lambda a, b: a + b)

        clean = sweep([])
        died = sweep([(0, 1), (2, 1)])
        assert np.array_equal(clean, died)
        # and the queue's own checkpoints are restorable
        q3 = WorkQueue(shards, result_template=tpl, clock=ManualClock())
        assert q3.resume(tmp_path / "q0")
        assert q3.finished
        assert np.array_equal(q3.merge(lambda a, b: a + b), clean)


# ------------------------------------------------------------ supervisor
class TestSupervisor:
    def test_gives_up_after_max_restarts(self, host, tmp_path):
        sem, _ = views(host)
        plan = FailurePlan({k: "crash" for k in range(0, 40)})
        with pytest.raises(DeviceFailure, match="gave up"):
            run_supervised(sem, PageRankPullProgram(tol=1e-4),
                           max_supersteps=25,
                           checkpoint=CheckpointSpec(tmp_path, every_k=2),
                           plan=plan, max_restarts=3)

    def test_report_records_resume_points(self, host, tmp_path):
        sem, _ = views(host)
        res, rep = run_supervised(
            sem, PageRankPullProgram(tol=1e-4), max_supersteps=25,
            checkpoint=CheckpointSpec(tmp_path, every_k=4),
            plan=FailurePlan({1: "crash", 9: "crash"}))
        assert rep.restarts == 2
        assert rep.resumed_steps == [None, 8]  # crash@1 pre-dates any save
        assert len(rep.log) == 2


# ------------------------------------------------------------ telemetry
class TestTelemetry:
    def test_sync_odometer(self, host, tmp_path):
        # The odometer records the checkpoint layer's synchronous seconds
        # and save count; equality/hash ignore it; child() phases share
        # (and accumulate into) the same dict.
        sem, _ = views(host)
        tele = {}
        spec = CheckpointSpec(tmp_path / "t", every_k=2, telemetry=tele)
        run_program(sem, PageRankPullProgram(tol=1e-4),
                    max_supersteps=10, checkpoint=spec)
        assert tele["saves"] >= 2
        assert tele["sync_s"] > 0.0
        assert spec.child("fwd").telemetry is tele
        assert spec == CheckpointSpec(tmp_path / "t", every_k=2)


# ------------------------------------------------------------ torn metadata
class TestTornMetadata:
    def test_latest_step_skips_torn_extra(self, tmp_path):
        tree = {"a": jnp.arange(4)}
        save_checkpoint(tmp_path, 2, tree, extra={"fp": "ok"})
        save_checkpoint(tmp_path, 4, tree, extra={"fp": "ok"})
        # a crash/bit-flip truncates step 4's metadata mid-write
        (tmp_path / "step_00000004" / "extra.json").write_text('{"fp": "o')
        assert latest_step(tmp_path) == 2  # skipped, not crashed on
        restored, step = restore_checkpoint(tmp_path,
                                            {"a": jnp.zeros(4, jnp.int32)})
        assert step == 2

    def test_load_extra_raises_typed_error_naming_step(self, tmp_path):
        save_checkpoint(tmp_path, 7, {"a": jnp.zeros(1)}, extra={"x": 1})
        (tmp_path / "step_00000007" / "extra.json").write_text("")
        with pytest.raises(CheckpointCorruptionError, match="step 7"):
            load_extra(tmp_path, 7)

    def test_queue_resume_survives_torn_newest_snapshot(self, tmp_path):
        """End to end: the newest queue snapshot's metadata is torn; the
        resume scan falls back one step instead of crashing, and the
        sweep still finishes with the canonical merge."""
        shards = shard_sources(np.arange(23), 5)
        full = run_workers(
            WorkQueue(shards, result_template=np.zeros(16),
                      clock=ManualClock()), _work).merge(lambda a, b: a + b)
        q = WorkQueue(shards, result_template=np.zeros(16),
                      clock=ManualClock())
        for _ in range(3):
            l = q.lease()
            q.complete(l, _work(l.payload))
            q.checkpoint(tmp_path, keep=5)
        torn = tmp_path / "step_00000003" / "extra.json"
        torn.write_text(torn.read_text()[:10])
        q2 = WorkQueue(shards, result_template=np.zeros(16),
                       clock=ManualClock())
        assert q2.resume(tmp_path)
        assert int(q2.completed.sum()) == 2  # one step of progress lost
        run_workers(q2, _work)
        assert np.array_equal(full, q2.merge(lambda a, b: a + b))


# ------------------------------------------------------------ streaming store
class TestStreamingStore:
    def test_sharded_save_bounded_staging_bitwise_restore(self, tmp_path):
        """State >> max_shard_bytes: many fsync'd shards, measured peak
        staging <= one shard budget, restore bitwise across dtypes."""
        rng = np.random.default_rng(0)
        tree = {
            "big": rng.standard_normal(16384).astype(np.float32),  # 64 KiB
            "ints": np.arange(5000, dtype=np.int64),
            "flags": rng.random(333) < 0.5,
            "scalar": np.float64(1.25),
        }
        tel = {}
        budget = 8192
        save_checkpoint(tmp_path, 3, tree, max_shard_bytes=budget,
                        telemetry=tel)
        shards = sorted((tmp_path / "step_00000003").glob("shard_*.npz"))
        assert len(shards) >= 8  # 64K floats alone need 8 shards
        assert 0 < tel["stage_peak_bytes"] <= budget
        assert tel["shard_files"] == len(shards)
        restored, step = restore_checkpoint(tmp_path, tree, as_numpy=True)
        assert step == 3
        for k, v in tree.items():
            assert np.array_equal(np.asarray(restored[k]), np.asarray(v)), k
            assert np.asarray(restored[k]).dtype == np.asarray(v).dtype

    def test_streaming_handles_jax_and_mldtype_leaves(self, tmp_path):
        tree = {"bf": jnp.arange(3000, dtype=jnp.bfloat16),
                "f": jnp.linspace(0, 1, 700)}
        save_checkpoint(tmp_path, 1, tree, max_shard_bytes=1024)
        restored, _ = restore_checkpoint(tmp_path, tree)
        for k in tree:
            assert restored[k].dtype == tree[k].dtype
            assert np.array_equal(np.asarray(restored[k]),
                                  np.asarray(tree[k])), k

    def test_delta_skips_unchanged_pieces(self, tmp_path):
        """Second snapshot of a mostly-unchanged state stores only the
        changed pieces; restore resolves references to the base step."""
        import json
        tree = {"big": np.arange(8192, dtype=np.float32),
                "tick": np.int64(0)}
        save_checkpoint(tmp_path, 1, tree, max_shard_bytes=4096, delta=True)
        full_bytes = json.loads(
            (tmp_path / "step_00000001" / "manifest.json").read_text()
        )["stored_bytes"]
        tree2 = dict(tree)
        tree2["tick"] = np.int64(1)  # only the odometer moved
        save_checkpoint(tmp_path, 2, tree2, max_shard_bytes=4096, delta=True)
        m2 = json.loads(
            (tmp_path / "step_00000002" / "manifest.json").read_text())
        assert m2["stored_bytes"] * 2 < full_bytes  # >=2x smaller
        # pieces of the unchanged leaf reference step 1's physical copy
        refs = {p["step"] for p in m2["leaves"][0]["pieces"]}
        assert refs == {1}
        restored, step = restore_checkpoint(tmp_path, tree2, as_numpy=True)
        assert step == 2
        assert np.array_equal(restored["big"], tree2["big"])
        assert int(restored["tick"]) == 1

    def test_delta_references_collapse_to_physical_home(self, tmp_path):
        """A long delta chain never deepens: step k references the step
        that STORES each piece, not step k-1 — restore is one hop."""
        import json
        tree = {"big": np.zeros(4096, np.float32), "t": np.int64(0)}
        for s in range(1, 6):
            tree = dict(tree, t=np.int64(s))
            save_checkpoint(tmp_path, s, tree, delta=True)
        m = json.loads(
            (tmp_path / "step_00000005" / "manifest.json").read_text())
        assert {p["step"] for p in m["leaves"][0]["pieces"]} == {1}
        restored, _ = restore_checkpoint(tmp_path, tree, as_numpy=True)
        assert int(restored["t"]) == 5

    def test_gc_retains_delta_referenced_base(self, tmp_path):
        """Retention keeps a step alive while newer snapshots reference
        its shards — deleting it would orphan every delta above it."""
        mgr = CheckpointManager(tmp_path, keep=2, delta=True,
                                max_shard_bytes=4096)
        tree = {"big": np.arange(4096, dtype=np.float32), "t": np.int64(0)}
        for s in range(6):
            mgr.save(s, dict(tree, t=np.int64(s)))
        kept = sorted(p.name for p in tmp_path.iterdir()
                      if p.name.startswith("step_"))
        assert "step_00000000" in kept  # the physical home survives
        restored, step = mgr.restore(tree)
        assert step == 5
        assert np.array_equal(np.asarray(restored["big"]), tree["big"])

    def test_missing_referenced_shard_is_corruption_error(self, tmp_path):
        tree = {"big": np.zeros(4096, np.float32), "t": np.int64(0)}
        save_checkpoint(tmp_path, 1, tree, delta=True)
        save_checkpoint(tmp_path, 2, dict(tree, t=np.int64(1)), delta=True)
        shutil.rmtree(tmp_path / "step_00000001")  # deleted out of band
        with pytest.raises(CheckpointCorruptionError, match="shard"):
            restore_checkpoint(tmp_path, tree, 2)

    def test_spec_threads_streaming_delta_through_driver(self, host,
                                                         tmp_path):
        """CheckpointSpec(max_shard_bytes, delta) reach the BSP driver's
        snapshots, and kill-resume parity still holds bitwise."""
        sem, _ = views(host)
        prog = PageRankPullProgram(tol=1e-4)
        base = run_program(sem, prog, max_supersteps=25)
        res, rep = run_supervised(
            sem, prog, max_supersteps=25,
            checkpoint=CheckpointSpec(tmp_path / "d", every_k=3,
                                      max_shard_bytes=2048, delta=True,
                                      async_save=False),
            plan=FailurePlan({7: "crash"}))
        assert rep.restarts == 1
        assert_identical(base, res)
        import json
        steps = sorted((tmp_path / "d").glob("step_*/manifest.json"))
        assert steps, "driver produced no streaming snapshots"
        m = json.loads(steps[-1].read_text())
        assert m.get("format") == 2  # the streaming layout, not legacy

    def test_spec_validates_shard_bytes(self, tmp_path):
        with pytest.raises(ValueError):
            CheckpointSpec(tmp_path, max_shard_bytes=0)


# ------------------------------------------------- wall-clock lease expiry
class TestWallClockExpiry:
    def test_dead_worker_task_reissued_on_real_clock(self):
        """The default-clock contract, no ManualClock: a worker that goes
        silent past a real (tiny) lease_timeout loses its task to the
        next lease(), and its late result is a stale token."""
        import time as _time
        q = WorkQueue(shard_sources(np.arange(6), 3),
                      lease_timeout=0.1, result_template=np.zeros(16))
        assert q._clock is _time.monotonic  # the documented default
        l1 = q.lease()
        assert (l1.tid, l1.attempt) == (0, 1)
        _time.sleep(0.25)  # the worker is presumed dead
        l2 = q.lease()
        assert (l2.tid, l2.attempt) == (0, 2)  # re-issued, not stuck
        assert q.complete(l2, _work(l2.payload))
        assert not q.complete(l1, _work(l1.payload))  # late == stale
        assert q.completed[0]

    def test_late_complete_before_reap_still_commits(self):
        """Lazy expiry: an expired-but-unreaped lease can still commit —
        nothing observed the expiry, so the work is not wasted."""
        import time as _time
        q = WorkQueue(shard_sources(np.arange(3), 3),
                      lease_timeout=0.05, result_template=np.zeros(16))
        l1 = q.lease()
        _time.sleep(0.1)  # expired on the wall clock, but nobody reaped
        assert q.complete(l1, _work(l1.payload))


# ------------------------------------------------- batched stream retry
class TestBatchedStreamRetry:
    def test_transient_faults_absorbed_bitwise_per_query(self, host):
        """(n, Q) host-streamed run under transient stream faults: the
        retries land in IOStats.retries and NOTHING else moves — values,
        per-query supersteps, and every other ledger field are bitwise
        the fault-free run's."""
        from repro.core import run_program_batched

        _, hv = views(host)
        prog = BFSProgram()
        pol = ExecutionPolicy(residency="host", stream_backoff_s=0.0)
        seeds = jnp.asarray([0, 3, 11], jnp.int32)
        base = run_program_batched(hv, prog, pol, seeds=seeds)
        assert int(base.iostats.retries) == 0

        calls = [0]

        def flaky():  # two transient drops mid-sweep
            calls[0] += 1
            if calls[0] in (2, 4):
                raise OSError("transient link drop")

        with inject_stream_faults(flaky):
            res = run_program_batched(hv, prog, pol, seeds=seeds)
        assert int(res.iostats.retries) == 2
        assert_identical(base, res, skip=("retries",))
        assert np.array_equal(np.asarray(base.query_supersteps),
                              np.asarray(res.query_supersteps))

    def test_exhaustion_leaves_no_half_committed_checkpoint(self, host,
                                                            tmp_path):
        """StreamFailure after retry exhaustion mid-(n, Q) run: the
        checkpoint directory holds only COMPLETE snapshots (or none), and
        resuming from it converges to the bitwise fault-free result."""
        from repro.core import run_program_batched

        _, hv = views(host)
        prog = BFSProgram()
        seeds = jnp.asarray([0, 3, 11], jnp.int32)
        pol = ExecutionPolicy(residency="host", stream_retries=1,
                              stream_backoff_s=0.0)
        base = run_program_batched(hv, prog, pol, seeds=seeds)

        calls = [0]

        def dies_later():  # healthy start, then the link goes down hard
            calls[0] += 1
            if calls[0] >= 3:
                raise OSError("link down")

        d = tmp_path / "b"
        spec = CheckpointSpec(d, every_k=1, async_save=False)
        with inject_stream_faults(dies_later):
            with pytest.raises(StreamFailure):
                run_program_batched(hv, prog, pol, seeds=seeds,
                                    checkpoint=spec)
        step = latest_step(d)
        if step is not None:  # whatever was published must be restorable
            assert load_extra(d, step) is not None
        res = run_program_batched(hv, prog, pol, seeds=seeds,
                                  checkpoint=spec, resume=True)
        assert_identical(base, res, skip=("retries",))
        assert np.array_equal(np.asarray(base.query_supersteps),
                              np.asarray(res.query_supersteps))


# ------------------------------------------------------- checkpoint store
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray(3, jnp.int32)}}
    save_checkpoint(tmp_path, 5, tree)
    assert latest_step(tmp_path) == 5
    like = jax.tree_util.tree_map(jnp.zeros_like, tree)
    restored, step = restore_checkpoint(tmp_path, like)
    assert step == 5
    np.testing.assert_array_equal(restored["a"], tree["a"])
    assert int(restored["b"]["c"]) == 3


def test_checkpoint_atomicity_ignores_partial(tmp_path):
    tree = {"x": jnp.ones(4)}
    save_checkpoint(tmp_path, 1, tree)
    # simulate a crashed mid-write: a .tmp dir and a dir without manifest
    (tmp_path / "step_00000009.tmp").mkdir()
    (tmp_path / "step_00000007").mkdir()
    assert latest_step(tmp_path) == 1


def test_checkpoint_manager_async_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.full(3, float(s))}, blocking=(s % 2 == 0))
    mgr.wait()
    assert latest_step(tmp_path) == 4
    steps = sorted(
        int(d.name.split("_")[1]) for d in tmp_path.iterdir()
        if d.name.startswith("step_")
    )
    assert len(steps) <= 2  # retention
    restored, step = mgr.restore({"x": jnp.zeros(3)})
    assert step == 4 and float(restored["x"][0]) == 4.0
