"""Batched multi-source traversal: the (n, Q) query axis.

The contract pinned here: batching Q queries through one engine pass is a
pure I/O optimization — **bitwise invisible** in every answer.

  * **Sequential parity** — a batched multi-source BFS is bitwise-equal
    (values, per-query supersteps, IOStats counters) to Q independent
    single-source runs, across all four backends × both residencies.
    ``query_supersteps[q]`` equals query q's solo superstep count; the
    batched run's total is their max.
  * **Order invariance** — permuting the source list permutes the value
    columns and changes no IOStats counter (the union frontier, and so
    the fetch schedule, is permutation-invariant).
  * **Retirement** — converged query columns retire mid-run (live columns
    compact into pow2 buckets); a workload whose queries converge at
    wildly different supersteps still reassembles bitwise-equal columns.
  * **Fault tolerance** — an ``(n, Q)`` state checkpoints and resumes
    bitwise-equal to an uninterrupted run (frontier snapshots store the
    1-D union, so the recovery schema is width-independent).
  * **Amortization** — under ``residency='host'`` the per-query host-link
    bytes drop: Q batched queries move far fewer bytes than Q sequential
    runs.
  * **Queue composition** — ``shard_sources(batch=Q)`` payloads feed
    batched passes whose canonical-tid merge stays death-invariant.
"""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.algs.bfs import BFSProgram
from repro.algs.pagerank import PersonalizedPageRankProgram
from repro.core import (
    CheckpointSpec,
    ExecutionPolicy,
    IOStats,
    ManualClock,
    WorkQueue,
    run_program,
    run_program_batched,
    run_workers,
    shard_sources,
)
from repro.core.recovery import DeviceFailure, FailurePlan
from repro.graph.generators import rmat

pytestmark = pytest.mark.kernel

BACKENDS = ("scan", "compact", "blocked", "blocked_compact")
SOURCES = (0, 5, 17, 99)


def _policy(backend, residency="device"):
    return ExecutionPolicy(backend=backend, chunk_cap=8,
                           switch_fraction=None, residency=residency)


@pytest.fixture(scope="module")
def session():
    g = rmat(8, edge_factor=8, seed=2, symmetrize=True)
    return repro.Graph(g, chunk_size=128, bd=32, bs=32)


def _io_tuple(io: IOStats, *, skip=("queries",)):
    return tuple(int(v) for f, v in zip(io._fields, io) if f not in skip)


# ------------------------------------------------------- sequential parity
class TestSequentialParity:
    @pytest.mark.parametrize("residency", ["device", "host"])
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_bfs_batched_equals_q_solo_runs(self, session, backend, residency):
        pol = _policy(backend, residency)
        sem = session._sem(pol, BFSProgram())
        seeds = jnp.asarray(SOURCES, jnp.int32)
        res = run_program_batched(sem, BFSProgram(), pol, seeds=seeds)
        assert int(res.iostats.queries) == len(SOURCES)
        solo_steps = []
        for q in range(len(SOURCES)):
            solo = run_program(sem, BFSProgram(), pol, seeds=seeds[q:q + 1])
            assert (np.asarray(res.values[:, q])
                    == np.asarray(solo.values[:, 0])).all()
            assert int(res.query_supersteps[q]) == int(solo.supersteps)
            solo_steps.append(int(solo.supersteps))
        assert int(res.supersteps) == max(solo_steps)

    def test_bfs_batched_equals_plain_driver(self, session):
        # The unbatched driver runs the same (n, Q) program (union
        # dispatch lives in traverse, not the driver): bitwise-equal
        # values AND IOStats counters, so batching changes labels only.
        pol = _policy("scan")
        sem = session._sem(pol, BFSProgram())
        seeds = jnp.asarray(SOURCES, jnp.int32)
        batched = run_program_batched(sem, BFSProgram(), pol, seeds=seeds)
        plain = run_program(sem, BFSProgram(), pol, seeds=seeds)
        assert (np.asarray(batched.values) == np.asarray(plain.values)).all()
        assert int(batched.supersteps) == int(plain.supersteps)
        assert _io_tuple(batched.iostats) == _io_tuple(plain.iostats)
        assert int(plain.iostats.queries) == 0  # stamp is batched-only

    @pytest.mark.parametrize("residency", ["device", "host"])
    def test_ppr_batched_equals_width_one_runs(self, session, residency):
        pol = _policy("scan", residency)
        prog = PersonalizedPageRankProgram(tol=1e-3)
        sem = session._sem(pol, prog)
        seeds = jnp.asarray(SOURCES, jnp.int32)
        res = run_program_batched(sem, prog, pol, seeds=seeds)
        assert res.values.shape == (session.n, len(SOURCES))
        for q in range(len(SOURCES)):
            solo = run_program_batched(sem, prog, pol, seeds=seeds[q:q + 1])
            assert (np.asarray(res.values[:, q])
                    == np.asarray(solo.values[:, 0])).all()
            assert int(res.query_supersteps[q]) == int(solo.supersteps)

    def test_order_invariance(self, session):
        pol = _policy("compact")
        sem = session._sem(pol, BFSProgram())
        perm = [2, 0, 3, 1]
        a = run_program_batched(sem, BFSProgram(), pol,
                                seeds=jnp.asarray(SOURCES, jnp.int32))
        b = run_program_batched(
            sem, BFSProgram(), pol,
            seeds=jnp.asarray([SOURCES[p] for p in perm], jnp.int32))
        assert (np.asarray(b.values)
                == np.asarray(a.values)[:, perm]).all()
        assert (np.asarray(b.query_supersteps)
                == np.asarray(a.query_supersteps)[perm]).all()
        assert _io_tuple(a.iostats, skip=()) == _io_tuple(b.iostats, skip=())


# ------------------------------------------------------------- retirement
class TestRetirement:
    def test_mixed_convergence_retires_columns(self, session):
        # Vertex with no out-edges? Use repeated near/far sources so some
        # queries converge supersteps earlier than others: retirement
        # (pow2 column compaction) must keep every column bitwise-equal
        # to its solo run, in the original source order.
        pol = _policy("scan")
        prog = PersonalizedPageRankProgram(tol=1e-3)
        sem = session._sem(pol, prog)
        n = session.n
        # per-query reset distributions with very different support sizes
        # converge at different supersteps, forcing mid-run retirement.
        rng = np.random.default_rng(0)
        resets = np.zeros((n, 5), np.float32)
        resets[0, 0] = 1.0
        resets[:, 1] = 1.0
        resets[rng.choice(n, 7, replace=False), 2] = 1.0
        resets[5, 3] = 1.0
        resets[:128, 4] = 1.0
        res = run_program_batched(sem, prog, pol, seeds=jnp.asarray(resets))
        steps = np.asarray(res.query_supersteps)
        assert steps.min() < steps.max()  # retirement actually exercised
        assert int(res.supersteps) == steps.max()
        for q in range(5):
            solo = run_program_batched(sem, prog, pol,
                                       seeds=jnp.asarray(resets[:, q:q + 1]))
            assert (np.asarray(res.values[:, q])
                    == np.asarray(solo.values[:, 0])).all(), f"query {q}"
            assert steps[q] == int(solo.supersteps)


# --------------------------------------------------------- fault tolerance
class TestCheckpointedBatch:
    def test_kill_resume_bitwise(self, session, tmp_path):
        pol = _policy("scan")
        sem = session._sem(pol, BFSProgram())
        seeds = jnp.asarray(SOURCES, jnp.int32)
        full = run_program_batched(sem, BFSProgram(), pol, seeds=seeds)
        ck = CheckpointSpec(str(tmp_path / "bfs"), every_k=1)
        with pytest.raises(DeviceFailure):
            run_program_batched(sem, BFSProgram(), pol, seeds=seeds,
                                checkpoint=ck, _plan=FailurePlan({3: "crash"}))
        res = run_program_batched(sem, BFSProgram(), pol, seeds=seeds,
                                  checkpoint=ck, resume=True)
        assert (np.asarray(res.values) == np.asarray(full.values)).all()
        assert int(res.supersteps) == int(full.supersteps)
        assert (np.asarray(res.query_supersteps)
                == np.asarray(full.query_supersteps)).all()
        assert _io_tuple(res.iostats, skip=()) == \
            _io_tuple(full.iostats, skip=())

    def test_float_state_kill_resume_bitwise(self, session, tmp_path):
        pol = _policy("scan")
        prog = PersonalizedPageRankProgram(tol=1e-3)
        sem = session._sem(pol, prog)
        seeds = jnp.asarray(SOURCES, jnp.int32)
        full = run_program_batched(sem, prog, pol, seeds=seeds)
        ck = CheckpointSpec(str(tmp_path / "ppr"), every_k=4)
        with pytest.raises(DeviceFailure):
            run_program_batched(sem, prog, pol, seeds=seeds, checkpoint=ck,
                                _plan=FailurePlan({20: "crash"}))
        res = run_program_batched(sem, prog, pol, seeds=seeds,
                                  checkpoint=ck, resume=True)
        assert (np.asarray(res.values) == np.asarray(full.values)).all()
        assert (np.asarray(res.query_supersteps)
                == np.asarray(full.query_supersteps)).all()


# ------------------------------------------------------------ amortization
class TestAmortization:
    def test_host_bytes_per_query_drop(self, session):
        pol = _policy("scan", "host")
        sem = session._sem(pol, BFSProgram())
        seeds = jnp.asarray(SOURCES, jnp.int32)
        batched = run_program_batched(sem, BFSProgram(), pol, seeds=seeds)
        seq = sum(
            int(run_program(sem, BFSProgram(), pol,
                            seeds=seeds[q:q + 1]).iostats.host_bytes)
            for q in range(len(SOURCES))
        )
        # one streamed tile serves all Q queries: the batched sweep's
        # host-link traffic must be well under the sequential total.
        assert int(batched.iostats.host_bytes) * 2 < seq


# ------------------------------------------------------------- the façade
class TestFacade:
    def test_bfs_multi_source(self, session):
        pol = _policy("scan")
        res = session.bfs(list(SOURCES), policy=pol)
        assert int(res.iostats.queries) == len(SOURCES)
        assert res.query_supersteps is not None
        for q, s in enumerate(SOURCES):
            solo = session.bfs(s, policy=pol)
            assert (np.asarray(res.values[:, q])
                    == np.asarray(solo.values)).all()
            assert int(res.query_supersteps[q]) == int(solo.supersteps)

    def test_pagerank_reset(self, session):
        pol = _policy("scan")
        res = session.pagerank(reset=list(SOURCES), policy=pol)
        assert res.values.shape == (session.n, len(SOURCES))
        assert int(res.iostats.queries) == len(SOURCES)
        # column q is query q's personalized fixed point, bitwise
        solo = session.pagerank(reset=[SOURCES[2]], policy=pol)
        assert (np.asarray(res.values[:, 2])
                == np.asarray(solo.values[:, 0])).all()
        with pytest.raises(ValueError, match="push"):
            session.pagerank(reset=[0], mode="pull")

    def test_betweenness_uni_batched(self, session):
        pol = _policy("scan")
        srcs = jnp.asarray(SOURCES, jnp.int32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            seq = session.betweenness(srcs, mode="uni", policy=pol)
            bat = session.betweenness(srcs, mode="uni", batch=2, policy=pol)
        assert np.allclose(np.asarray(bat.values), np.asarray(seq.values),
                           rtol=1e-5, atol=1e-6)
        assert int(bat.iostats.queries) == len(SOURCES)
        # two-source groups amortize each group's chunk fetches
        assert int(bat.iostats.records) < int(seq.iostats.records)
        with pytest.raises(ValueError, match="uni"):
            session.betweenness(srcs, mode="multi", batch=2, policy=pol)

    def test_run_batch_width_mismatch(self, session):
        with pytest.raises(ValueError, match="batch=3"):
            session.run(BFSProgram(), seeds=jnp.asarray(SOURCES, jnp.int32),
                        batch=3, policy=_policy("scan"))

    def test_memory_report_query_state_term(self, session):
        r1 = session.memory_report(batch=1)
        r8 = session.memory_report(batch=8)
        assert r8["query_state_bytes"] == 8 * r1["query_state_bytes"]
        assert r1["query_state_bytes"] == 6 * session.n


# ------------------------------------------------------- queue composition
class TestQueueBatch:
    def test_shard_sources_batch(self):
        src = np.arange(10, dtype=np.int32)
        groups = shard_sources(src, batch=4)
        assert [len(g) for g in groups] == [4, 4, 2]
        assert (np.concatenate(groups) == src).all()
        with pytest.raises(ValueError, match="exactly one"):
            shard_sources(src, 4, batch=4)
        with pytest.raises(ValueError, match="exactly one"):
            shard_sources(src)

    def test_batched_merge_death_invariant(self, session):
        # Q-source groups leased as single tasks; a worker dying mid-group
        # loses the whole group's batched result, the retry recomputes it,
        # and the canonical-tid fold stays bitwise-identical to the
        # death-free (and to the sequential per-source) sweep.
        pol = _policy("scan")
        sem = session._sem(pol, BFSProgram())
        sources = np.asarray([0, 5, 17, 99, 3, 200], np.int32)

        def work(group):
            res = run_program_batched(sem, BFSProgram(), pol,
                                      seeds=jnp.asarray(group, jnp.int32))
            # reachable-vertex count per query: a float fold target
            return np.asarray(
                jnp.sum(res.values < np.iinfo(np.int32).max, axis=0),
                np.float64)

        tmpl = np.zeros((), np.float64)

        def fold(acc, r):
            return acc + float(np.sum(r))

        def sweep(deaths):
            q = WorkQueue(shard_sources(sources, batch=2),
                          lease_timeout=5.0, max_attempts=3,
                          result_template=np.zeros(2), clock=ManualClock())
            run_workers(q, work, deaths=deaths)
            return q.merge(fold, init=tmpl)

        clean = sweep(())
        died = sweep([(1, 1), (2, 1)])
        assert clean == died
        seq = sum(
            float(np.sum(np.asarray(
                run_program(sem, BFSProgram(), pol,
                            seeds=jnp.asarray([s], jnp.int32)).values)
                < np.iinfo(np.int32).max))
            for s in sources
        )
        assert clean == seq
