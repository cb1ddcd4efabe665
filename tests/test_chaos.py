"""Chaos gate: real OS worker processes, SIGKILLs and stalls mid-sweep.

The strongest multi-process claim the library makes: a multi-source sweep
served by N>=3 spawned worker processes — two of which are SIGKILL'd
mid-lease (no unwind, no flush) and one of which stalls past its lease
and tries a late commit — merges to *bitwise* the same result (values
plus the order-invariant IOStats ledger) as a crash-free single-process
run, across backends x residencies.  No task is lost, no task commits
twice, and the stale-token rejection count proves the race actually
happened rather than never being exercised.

The in-process :class:`DurableWorkQueue` protocol tests live here too:
they exercise the rename-arbitrated claim/reap/commit transitions that
the OS-level gate then stresses for real.
"""
import time
from pathlib import Path

import numpy as np
import pytest

import repro
from repro.core import (
    DurableWorkQueue,
    ExecutionPolicy,
    ManualClock,
    QueueMismatchError,
    run_workers,
    shard_sources,
)
from repro.core.workqueue import supervise_workers
from repro.graph.generators import rmat

pytestmark = pytest.mark.kernel

# 2 backends x both residencies — the sweep the chaos gate must hold on.
COMBOS = (
    ("scan", "device"),
    ("scan", "host"),
    ("compact", "device"),
    ("compact", "host"),
)
_N_SCALE = 6  # rmat scale: n = 64
_SHARD = 2
_SOURCES = np.arange(8)
_IO_FIELDS = 10  # len(IOStats._fields); checked in the gate test

# Per-worker-process caches (spawn children re-import this module fresh;
# workers persist across tasks, so the session compiles once per combo).
_session_cache: dict = {}


def _get_session():
    s = _session_cache.get("graph")
    if s is None:
        host = rmat(_N_SCALE, edge_factor=6, seed=3, symmetrize=True)
        s = repro.Graph(host, chunk_size=64, bd=32, bs=32)
        _session_cache["graph"] = s
    return s


def _slot_len(n: int) -> int:
    return n * _SHARD + _IO_FIELDS


def chaos_work(payload):
    """One task: a batched multi-source BFS on one (backend, residency)
    combo.  Payload = [combo_idx, src0, src1]; result = a flat float64
    vector, zero outside this combo's slot, holding the (n, Q) distance
    block and the task's IOStats ledger — so the queue's canonical
    additive merge yields per-combo sums of values and of the
    order-invariant I/O totals.  Must be module-level: spawn workers
    pickle it by reference."""
    p = np.asarray(payload, np.int64)
    combo_idx, srcs = int(p[0]), p[1:]
    backend, residency = COMBOS[combo_idx]
    s = _get_session()
    pol = ExecutionPolicy(backend=backend, residency=residency)
    r = s.bfs(np.asarray(srcs, np.int32), policy=pol)
    vals = np.asarray(r.values, np.float64).reshape(-1)
    io = np.asarray([float(v) for v in r.iostats], np.float64)
    out = np.zeros(len(COMBOS) * _slot_len(s.n), np.float64)
    a = combo_idx * _slot_len(s.n)
    out[a:a + vals.size] = vals
    out[a + s.n * _SHARD:a + s.n * _SHARD + io.size] = io
    return out


def _make_tasks() -> list:
    tasks = []
    for ci in range(len(COMBOS)):
        for grp in shard_sources(_SOURCES, _SHARD):
            tasks.append(np.concatenate([[ci], grp]).astype(np.int64))
    return tasks


# ------------------------------------------------------------ the OS gate
class TestChaosGate:
    def test_sigkill_chaos_bitwise_parity(self, tmp_path):
        """3 spawned workers, 2 SIGKILLs + 2 stalls mid-sweep, supervisor
        restarts — merged result bitwise-equal to a crash-free
        single-process run, per combo, with zero lost/double-committed
        tasks and >0 stale-token rejections."""
        from repro.core.sem import IOStats

        assert len(IOStats._fields) == _IO_FIELDS
        tasks = _make_tasks()
        n = 2 ** _N_SCALE
        tpl = np.zeros(len(COMBOS) * _slot_len(n), np.float64)

        # crash-free single-process baseline: one OS worker, no faults
        clean = DurableWorkQueue(tmp_path / "clean", tasks,
                                 lease_timeout=10.0, result_template=tpl)
        rep0 = run_workers(clean, chaos_work, processes=1, timeout=560.0)
        assert rep0.finished and rep0.completed == len(tasks)
        assert rep0.kills == 0 and rep0.stale_rejections == 0
        ref = clean.merge(lambda a, b: a + b)

        # chaos run: kills and stalls spread across combos
        faults = {
            (1, 1): "sigkill",   # combo 0 (scan/device)
            (9, 1): "sigkill",   # combo 2 (compact/device)
            (5, 1): 2.5,         # stall past the lease: combo 1 (scan/host)
            (14, 1): 2.5,        # stall: combo 3 (compact/host)
        }
        chaos = DurableWorkQueue(tmp_path / "chaos", tasks,
                                 lease_timeout=1.5, max_attempts=4,
                                 result_template=tpl)
        rep = run_workers(chaos, chaos_work, processes=3, faults=faults,
                          timeout=560.0)
        assert rep.finished, rep.log
        assert rep.kills >= 2 and rep.restarts >= 2
        assert rep.stale_rejections > 0  # the late commits were refused
        assert rep.dead_letters == []

        # no task lost, none double-committed: exactly one done marker per tid
        done = sorted(p.name for p in (tmp_path / "chaos" / "done").iterdir())
        assert len(done) == len(tasks)
        assert len({m.split(".")[0] for m in done}) == len(tasks)

        merged = chaos.merge(lambda a, b: a + b)
        for ci, (backend, residency) in enumerate(COMBOS):
            a = ci * _slot_len(n)
            seg_ref = ref[a:a + _slot_len(n)]
            seg = merged[a:a + _slot_len(n)]
            assert np.array_equal(seg, seg_ref), (
                f"chaos merge diverged on backend={backend} "
                f"residency={residency}")
        assert np.array_equal(merged, ref)


# ------------------------------------------------------- protocol (fast)
def _vec_work(payload):
    out = np.zeros(4, np.float64)
    out[:2] = np.asarray(payload, np.float64)
    return out


class TestDurableQueueProtocol:
    def make(self, root, **kw):
        kw.setdefault("result_template", np.zeros(4, np.float64))
        kw.setdefault("lease_timeout", 5.0)
        kw.setdefault("clock", ManualClock())
        return DurableWorkQueue(root, [np.array([i, i + 1])
                                       for i in range(5)], **kw)

    def test_claim_is_exclusive_across_attached_queues(self, tmp_path):
        q1 = self.make(tmp_path / "q")
        q2 = self.make(tmp_path / "q")  # attach: same root, same clock era
        l1, l2 = q1.lease(), q2.lease()
        assert {l1.tid, l2.tid} == {0, 1}  # the rename race never double-leases
        assert q1.complete(l1, _vec_work(l1.payload))
        assert q2.complete(l2, _vec_work(l2.payload))

    def test_expiry_reissue_and_stale_rejection(self, tmp_path):
        clock = ManualClock()
        q = self.make(tmp_path / "q", clock=clock)
        l1 = q.lease()
        assert (l1.tid, l1.attempt) == (0, 1)
        clock.advance(6.0)
        l2 = q.lease()  # reaps the expired claim, re-issues as attempt 2
        assert (l2.tid, l2.attempt) == (0, 2)
        assert q.complete(l2, _vec_work(l2.payload))
        # the presumed-dead worker's late commit is refused by the rename
        assert not q.complete(l1, _vec_work(l1.payload))
        assert q.stale_rejections == 1

    def test_renew_extends_lease(self, tmp_path):
        clock = ManualClock()
        q = self.make(tmp_path / "q", clock=clock, lease_timeout=5.0)
        l1 = q.lease()
        clock.advance(4.0)
        q.renew(l1)  # heartbeat: 4s in, extend to t=9
        clock.advance(4.0)
        others = [q.lease() for _ in range(4)]
        assert all(l is not None and l.tid != 0 for l in others)
        assert q.complete(l1, _vec_work(l1.payload))  # still ours at t=8

    def test_dead_letter_after_max_attempts(self, tmp_path):
        clock = ManualClock()
        q = self.make(tmp_path / "q", clock=clock, max_attempts=2)
        for expect in (1, 2):
            l = q.lease()
            assert (l.tid, l.attempt) == (0, expect)
            clock.advance(6.0)  # worker dies; lease expires
        q.lease()  # reap dead-letters tid 0, then claims tid 1
        assert q.dead_letters == [0]

    def test_fail_gives_back_early(self, tmp_path):
        q = self.make(tmp_path / "q")
        l1 = q.lease()
        assert q.fail(l1)
        l2 = q.lease()  # re-issued immediately, no timeout wait
        assert (l2.tid, l2.attempt) == (0, 2)

    def test_attach_resumes_progress_from_filesystem(self, tmp_path):
        q = self.make(tmp_path / "q")
        for _ in range(2):
            l = q.lease()
            q.complete(l, _vec_work(l.payload))
        # process dies here; a fresh attach sees the committed work
        q2 = self.make(tmp_path / "q")
        assert int(q2.completed.sum()) == 2
        while not q2.finished:
            l = q2.lease()
            q2.complete(l, _vec_work(l.payload))
        ref = np.zeros(4)
        for t in q.tasks:
            ref[:2] += t
        assert np.array_equal(q2.merge(lambda a, b: a + b), ref)

    def test_attach_rejects_different_task_set(self, tmp_path):
        self.make(tmp_path / "q")
        with pytest.raises(QueueMismatchError):
            DurableWorkQueue(tmp_path / "q", [np.array([9, 9])],
                             result_template=np.zeros(4))

    def test_merge_folds_committed_attempt_in_canonical_order(self, tmp_path):
        q = self.make(tmp_path / "q")
        leases = [q.lease() for _ in range(5)]
        for l in reversed(leases):  # completion order must not leak
            assert q.complete(l, _vec_work(l.payload))
        fwd = self.make(tmp_path / "q2")
        while not fwd.finished:
            l = fwd.lease()
            fwd.complete(l, _vec_work(l.payload))
        assert np.array_equal(q.merge(lambda a, b: a + b),
                              fwd.merge(lambda a, b: a + b))

    def test_wall_clock_expiry_with_real_processes_semantics(self, tmp_path):
        """Default clock (shared wall time): a worker that stops
        heartbeating loses its task to the next lease() after the
        timeout — no ManualClock, real seconds."""
        q = DurableWorkQueue(tmp_path / "q", [np.array([1, 2])],
                             lease_timeout=0.15,
                             result_template=np.zeros(4))
        l1 = q.lease()
        time.sleep(0.3)  # holder goes silent past the timeout
        l2 = q.lease()
        assert (l2.tid, l2.attempt) == (0, 2)
        assert q.complete(l2, _vec_work(l2.payload))
        assert not q.complete(l1, _vec_work(l1.payload))

    def test_run_workers_processes_requires_durable_queue(self):
        from repro.core import WorkQueue

        q = WorkQueue([np.array([0, 1])], result_template=np.zeros(4),
                      clock=ManualClock())
        with pytest.raises(TypeError, match="DurableWorkQueue"):
            run_workers(q, _vec_work, processes=2)

    def test_supervise_workers_requires_durable_queue(self):
        from repro.core import WorkQueue

        q = WorkQueue([np.array([0, 1])], result_template=np.zeros(4),
                      clock=ManualClock())
        with pytest.raises(TypeError):
            supervise_workers(q, _vec_work)

    def test_supervise_workers_refuses_when_parent_holds_tpu(
            self, tmp_path, monkeypatch):
        import jax

        q = self.make(tmp_path / "q")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="holds the TPU"):
            run_workers(q, _vec_work, processes=2)
