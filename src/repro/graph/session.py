"""``repro.Graph`` — the library façade (the paper's pip-installable pitch).

One object owns the whole workflow FlashGraph split across utilities: build
the graph image once (``from_edges`` / ``from_csr``), let the engine build
and **cache** its device-resident SEM views lazily (chunk stores on first
use, dense Pallas tile views only when a blocked backend asks, reverse tile
views only when a reverse flow asks — and each exactly once per session, so
back-to-back algorithm calls never re-tile the store), and run algorithms —
the six paper algorithms as methods, any user-defined
:class:`~repro.core.VertexProgram` through :meth:`Graph.run` — all
returning a uniform :class:`~repro.core.ProgramResult` and all driven by a
single :class:`~repro.core.ExecutionPolicy`.

    import numpy as np, repro

    g = repro.Graph.from_edges(src, dst, symmetrize=True)
    pr = g.pagerank()                       # ProgramResult(values, ...)
    bf = g.bfs(0, policy=repro.ExecutionPolicy(direction="auto"))
    cc = g.run(MyProgram())                 # your ~30-line algorithm

The façade adds no execution layer of its own: methods call
:func:`~repro.core.run_program` on the cached views, so a façade call
compiles to exactly the same XLA as a hand-driven program (the chip
benchmark, ``python3 bench/run.py``, times façade calls).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (
    ExecutionPolicy,
    IOStats,
    ProgramResult,
    SemGraph,
    run_program,
    run_program_batched,
)
from ..core.engine import p2p_caps
from ..core.program import VertexProgram, under_trace
from ..core.sem import _store_record_bytes, device_graph
from ..core.semiring import PLUS_TIMES
# Algorithm imports are eager: a lazy import executed during a user's first
# jitted façade call would run module bodies inside the trace (and any
# module-level jnp constant would leak as a tracer).
from ..algs.betweenness import FusedBCProgram, _bc_sync, _finish
from ..algs.bfs import BFSProgram
from ..algs.coreness import CorenessProgram
from ..algs.diameter import _diameter
from ..algs.louvain import louvain as _louvain
from ..algs.pagerank import (
    PageRankPullProgram,
    PageRankPushProgram,
    PersonalizedPageRankProgram,
)
from ..algs.sssp import SSSPProgram
from ..algs.triangles import TriangleResult, count_triangles
from . import csr

__all__ = ["Graph"]

_BLOCKED = ("blocked", "blocked_compact")


def _i32(value) -> jnp.ndarray:
    """Host counter -> int32 field, saturating instead of raising.

    The device-side IOStats counters wrap at 2^31 by documented contract;
    host-side ledgers (triangles, louvain) hold unbounded Python ints, and
    ``jnp.asarray(big, int32)`` would *crash* where the device path merely
    degrades — clamp so huge host runs stay usable."""
    return jnp.asarray(min(int(value), 2**31 - 1), jnp.int32)


def _host_result(values, *, supersteps=0, state=None,
                 requests=0, records=0, bytes_moved=0) -> ProgramResult:
    """Wrap a host-side algorithm's output in the uniform ProgramResult."""
    z = jnp.zeros((), jnp.int32)
    io = IOStats(
        requests=_i32(requests),
        records=_i32(records),
        chunks_skipped=z,
        messages=z,
        supersteps=_i32(supersteps),
        bytes_moved=_i32(bytes_moved),
        x_fetches=z,
        host_bytes=z,
        retries=z,
    )
    return ProgramResult(values, _i32(supersteps), io, state)


class Graph:
    """A graph session: host image + lazily cached device views + algorithms.

    Construction does no device work; every SEM view is built on first use
    and cached for the session's lifetime:

      * the *base* view (edge chunk stores + CSR arrays) on the first
        algorithm call;
      * the dense Pallas tile view per tile encoding ('plus_times' /
        'min_plus' / 'bool') the first time a ``backend='blocked*'``
        policy needs it;
      * the transposed tile view the first time a reverse flow
        (betweenness backward) runs blocked.

    Args:
      host: the immutable CSR image (:class:`repro.graph.csr.Graph`).
      chunk_size: SEM edge-chunk size (fetch/skip granularity).
      bd / bs: dense tile dims for the blocked Pallas backends.
    """

    def __init__(self, host: csr.Graph, *, chunk_size: int = 4096,
                 bd: int = 128, bs: int = 128):
        self._host = host
        self._chunk_size = chunk_size
        self._bd, self._bs = bd, bs
        self._base: Optional[SemGraph] = None
        self._tiles: dict = {}  # (semiring, reverse, tile_order) -> BlockedGraph
        self._views: dict = {}  # (semiring, with_reverse, tile_order) -> SemGraph
        self._host_view = None  # the one residency='host' view (lazy)

    # ------------------------------------------------------------- build
    @classmethod
    def from_edges(
        cls,
        src,
        dst,
        n: Optional[int] = None,
        weights=None,
        *,
        symmetrize: bool = False,
        dedup: bool = True,
        drop_self_loops: bool = True,
        chunk_size: int = 4096,
        bd: int = 128,
        bs: int = 128,
    ) -> "Graph":
        """Build a session from a COO edge list (see
        :func:`repro.graph.csr.from_edges` for the cleaning semantics)."""
        host = csr.from_edges(
            src, dst, n=n, weights=weights, symmetrize=symmetrize,
            dedup=dedup, drop_self_loops=drop_self_loops,
        )
        return cls(host, chunk_size=chunk_size, bd=bd, bs=bs)

    @classmethod
    def from_csr(
        cls,
        indptr,
        indices,
        weights=None,
        *,
        chunk_size: int = 4096,
        bd: int = 128,
        bs: int = 128,
    ) -> "Graph":
        """Build a session from CSR arrays (out-edges; the in-edge view the
        pull/auto policies need is derived here, once)."""
        indptr = np.asarray(indptr, np.int64)
        indices = np.asarray(indices, np.int32)
        n = int(indptr.shape[0] - 1)
        src = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
        host = csr.from_edges(src, indices, n=n, weights=weights,
                              dedup=False, drop_self_loops=False)
        return cls(host, chunk_size=chunk_size, bd=bd, bs=bs)

    # ------------------------------------------------------------- views
    @property
    def host(self) -> csr.Graph:
        """The immutable host CSR image."""
        return self._host

    @property
    def n(self) -> int:
        return self._host.n

    @property
    def m(self) -> int:
        return self._host.m

    def __repr__(self) -> str:
        built = sorted(k for k, v in (("base", self._base),) if v is not None)
        built += [f"tiles{k}" for k in sorted(self._tiles)]
        return (f"Graph(n={self.n}, m={self.m}, chunk_size={self._chunk_size},"
                f" cached={built or 'none'})")

    def device(self, *, blocked: bool = False, blocked_reverse: bool = False,
               blocked_semiring: str = "plus_times",
               tile_order: str = "dest") -> SemGraph:
        """The cached device-resident SEM view (build-once per session).

        The base view (chunk stores + CSR) is shared by every composed
        view; blocked tile views are sub-cached per (encoding, direction,
        tile_order) so upgrading a view — e.g. a later call needing the
        reverse tiles, or a ``tile_order='hilbert'`` policy after a
        ``'dest'`` run — reuses every tile view already built and holds
        exactly one copy per order.

        Views are built under ``ensure_compile_time_eval``: the session
        outlives any single trace, so a cache populated during a user's
        jitted call must hold concrete arrays, not that trace's constants.
        """
        if self._base is None:
            with jax.ensure_compile_time_eval():
                self._base = device_graph(self._host,
                                          chunk_size=self._chunk_size)
        if not blocked and not blocked_reverse:
            return self._base
        key = (blocked_semiring, bool(blocked_reverse), tile_order)
        if key not in self._views:
            self._views[key] = dataclasses.replace(
                self._base,
                out_blocked=self._tile_view(blocked_semiring, reverse=False,
                                            tile_order=tile_order),
                out_blocked_rev=(
                    self._tile_view(blocked_semiring, reverse=True,
                                    tile_order=tile_order)
                    if blocked_reverse else None
                ),
            )
        return self._views[key]

    def _tile_view(self, semiring: str, *, reverse: bool,
                   tile_order: str = "dest"):
        key = (semiring, reverse, tile_order)
        if key not in self._tiles:
            from ..kernels.spmv import build_blocked

            with jax.ensure_compile_time_eval():
                self._tiles[key] = build_blocked(
                    self._host, bd=self._bd, bs=self._bs, direction="out",
                    semiring=semiring, reverse=reverse, tile_order=tile_order,
                )
        return self._tiles[key]

    def host_view(self):
        """The cached host-resident SEM view (``residency='host'``).

        Lazy like every other view, and keyed separately: a host session
        never touches ``device()``, so the O(m) device copy is never
        built.  Blocked tile stores are sub-cached inside the view per
        (encoding, direction, tile_order), mirroring the device cache.
        """
        if self._host_view is None:
            from ..core.residency import host_graph

            self._host_view = host_graph(self._host,
                                         chunk_size=self._chunk_size,
                                         bd=self._bd, bs=self._bs)
        return self._host_view

    def memory_report(self, policy: Optional[ExecutionPolicy] = None, *,
                      batch: int = 1) -> dict:
        """Where this session's graph bytes live right now.

        Returns a dict with

          * ``device_views`` — bytes per cached device view (``'base'``
            plus one ``'tiles:<encoding>:<fwd|rev>:<order>'`` entry per
            tile view), de-duplicated by array identity (composed views
            share the base arrays);
          * ``device_total`` — their sum;
          * ``device_edge_total`` — the O(m) subset: edge chunk stores,
            CSR index/weight columns, and tile views.  The SEM claim is
            about THIS number: ``residency='host'`` keeps it at 0;
          * ``host_store_bytes`` — host-pinned edge-store bytes;
          * ``peak_stage_bytes`` — largest measured in-flight staging
            footprint (≤ two ``stream_buffer`` batches by construction);
          * ``stream_buffer_bytes`` — the model size of ONE staging batch
            under ``policy`` (tile batches for blocked backends, chunk
            batches otherwise; when the p2p sparse arm is enabled its
            exact-``ecap``-lane single-shot payload is folded in as a
            ``max`` term, since bitwise scatter parity forbids splitting
            it).  Peak staging is ≤ 2 of these, with one caveat: a
            blocked accumulator run is never split (bitwise parity
            demands it), so a run longer than ``stream_buffer`` tiles
            becomes an oversized batch — runs are at most
            ``ceil(n / bs)`` tiles, so the bound is unconditional once
            ``stream_buffer`` reaches that;
          * ``query_state_bytes`` — the O(n·Q) vertex-state term for a
            ``batch=Q`` multi-source run (model: per vertex-query lane
            one bool frontier mask, one bool membership mask, and one
            4-byte value column — the BFS/PPR shape).  This is the axis
            the batched driver grows: edge bytes are amortized over Q
            but state is Q× a single query's, so Q is bounded by vertex
            memory, not edge bandwidth.
        """
        pol = policy if policy is not None else ExecutionPolicy()

        def _nbytes(tree, seen) -> int:
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                if hasattr(leaf, "nbytes") and id(leaf) not in seen:
                    seen.add(id(leaf))
                    total += int(leaf.nbytes)
            return total

        seen: set = set()
        device_views = {}
        if self._base is not None:
            device_views["base"] = _nbytes(self._base, seen)
        for (sr, rev, order), tv in sorted(self._tiles.items(),
                                           key=lambda kv: repr(kv[0])):
            name = f"tiles:{sr}:{'rev' if rev else 'fwd'}:{order}"
            device_views[name] = _nbytes(tv, seen)

        edge_seen: set = set()
        device_edge_total = 0
        if self._base is not None:
            for part in (self._base.out_store, self._base.in_store,
                         self._base.indices, self._base.w,
                         self._base.in_indices, self._base.in_w):
                if part is not None:
                    device_edge_total += _nbytes(part, edge_seen)
        for tv in self._tiles.values():
            device_edge_total += _nbytes(tv, edge_seen)

        B = pol.stream_buffer
        if pol.backend in _BLOCKED:
            # tile batches round up to a power of two of steps; each step
            # ships its tile plus six int32 schedule flags (+ one count).
            G = 1
            while G < B:
                G *= 2
            stream_buffer_bytes = G * (self._bd * self._bs * 4 + 6 * 4) + 4
        else:
            # chunk batches ship record columns plus one validity flag
            # per slot.
            stream_buffer_bytes = (
                B * (self._chunk_size
                     * _store_record_bytes(self._host.weights) + 1)
            )
        if pol.switch_fraction is not None:
            # the p2p sparse arm ships its exact-ecap-lane payload in ONE
            # piece (bitwise scatter parity needs the device's static lane
            # shape), so its single staged batch — not double-buffered —
            # can exceed the chunk/tile batch model.
            _, ecap, _ = p2p_caps(pol, self.n, self._host.m)
            lane = 9 + (4 if self._host.weights is not None else 0)
            stream_buffer_bytes = max(stream_buffer_bytes, ecap * lane)
        hv = self._host_view
        return {
            "residency": pol.residency,
            "device_views": device_views,
            "device_total": sum(device_views.values()),
            "device_edge_total": device_edge_total,
            "host_store_bytes": hv.store_nbytes if hv is not None else 0,
            "peak_stage_bytes": hv.peak_stage_bytes if hv is not None else 0,
            "stream_buffer_bytes": int(stream_buffer_bytes),
            "query_state_bytes": int(self.n) * max(int(batch), 1) * 6,
        }

    def _sem(self, policy: Optional[ExecutionPolicy], prog=None, *,
             need_reverse: bool = False) -> SemGraph:
        """The view a (program, policy) pair needs, built/cached on demand.

        Views are keyed on residency first: a host-residency policy gets
        the host view and never builds (or falls back to) a device copy.
        """
        if policy is not None and policy.residency == "host":
            return self.host_view()
        if policy is None or policy.backend not in _BLOCKED:
            return self.device()
        sr = getattr(prog, "semiring", None) or PLUS_TIMES
        if sr.name == "or_and":
            # Boolean frontiers run on plus_times tiles unless real weights
            # could corrupt the y>0 threshold — then exact occupancy tiles.
            tile_sr = "bool" if self._host.weights is not None else "plus_times"
        elif sr.name == "min_plus":
            tile_sr = "min_plus"
        else:
            tile_sr = "plus_times"
        need_reverse = need_reverse or getattr(prog, "reverse", False)
        return self.device(blocked=True, blocked_reverse=need_reverse,
                           blocked_semiring=tile_sr,
                           tile_order=policy.tile_order)

    # ------------------------------------------------------------- runner
    def run(
        self,
        program: VertexProgram,
        *,
        seeds=None,
        batch: Optional[int] = None,
        policy: Optional[ExecutionPolicy] = None,
        max_supersteps: Optional[int] = None,
        checkpoint=None,
        resume: bool = False,
        analyze: bool = False,
    ) -> ProgramResult:
        """Run any :class:`~repro.core.VertexProgram` on this graph.

        This is the extension point: the program sees the same engine —
        and the same cached views — as the built-in algorithms.  See
        ``examples/custom_program.py`` for a complete ~30-line program.

        ``batch=Q`` opts into the batched multi-source driver
        (:func:`~repro.core.run_program_batched`): the program must carry
        an ``(n, Q)`` frontier; the result gains per-query
        ``query_supersteps`` and ``iostats.queries == Q``, and converged
        query columns are retired mid-run.  ``Q`` must match the
        frontier's trailing axis.

        ``checkpoint=CheckpointSpec(dir)`` makes the run fault-tolerant
        (superstep snapshots; ``resume=True`` continues a killed run,
        bitwise-equal to an uninterrupted one).  The spec's
        ``max_shard_bytes=`` streams each snapshot in fsync'd shards
        with peak host staging bounded by one shard, and ``delta=True``
        stores only state pieces whose content changed since the
        previous snapshot — both flow through every façade method and
        the batched driver unchanged.  See :mod:`repro.core.recovery`
        and :mod:`repro.checkpoint.store`.

        ``analyze=True`` runs the static SEM contract checker
        (:func:`repro.analysis.check`) over the program+policy pair
        before any edge byte moves, raising
        :class:`~repro.analysis.AnalysisError` on error-severity
        findings.  The check is a one-time trace-level cost (cached per
        graph/program/policy); it adds zero per-superstep work.
        """
        pol = policy if policy is not None else program.default_policy
        if analyze:
            from repro import analysis as _analysis
            _analysis.check(self, program, pol, seeds=seeds,
                            raise_on_error=True)
        sem = self._sem(pol, program)
        if batch is not None:
            res = run_program_batched(sem, program, policy, seeds=seeds,
                                      max_supersteps=max_supersteps,
                                      checkpoint=checkpoint, resume=resume)
            q = int(res.iostats.queries)
            if int(batch) != q:
                raise ValueError(
                    f"batch={batch} does not match the program's query "
                    f"axis (frontier carries Q={q} columns)"
                )
            return res
        return run_program(sem, program, policy, seeds=seeds,
                           max_supersteps=max_supersteps,
                           checkpoint=checkpoint, resume=resume)

    # ------------------------------------------------------- the library
    def bfs(
        self,
        sources=0,
        *,
        policy: Optional[ExecutionPolicy] = None,
        max_supersteps: Optional[int] = None,
        checkpoint=None,
        resume: bool = False,
    ) -> ProgramResult:
        """(Multi-source) BFS.  ``values``: int32 distances —
        ``[n]`` for a scalar source, ``[n, K]`` for K sources
        (:data:`~repro.algs.UNREACHED` where a lane never arrives).

        ``direction='auto'`` policies get Beamer push↔pull switching;
        blocked backends stream all K lanes through one tile fetch.

        Multi-source calls run on the batched multi-source driver: the
        result additionally carries ``query_supersteps`` (int32[K] — the
        superstep each source's search converged at, equal to its solo
        run's superstep count) and ``iostats.queries == K``, so any other
        IOStats field divided by ``K`` is the per-query amortized cost.
        Values are bitwise-identical to K independent runs either way.
        """
        return self._search(BFSProgram(), sources, policy=policy,
                            max_supersteps=max_supersteps,
                            checkpoint=checkpoint, resume=resume)

    def sssp(
        self,
        sources=0,
        *,
        policy: Optional[ExecutionPolicy] = None,
        max_supersteps: Optional[int] = None,
        checkpoint=None,
        resume: bool = False,
    ) -> ProgramResult:
        """(Multi-source) weighted shortest paths by frontier Bellman-Ford
        (:class:`~repro.algs.SSSPProgram`).  ``values``: float32
        distances — ``[n]`` for a scalar source, ``[n, K]`` for K sources
        (``inf`` where a lane never arrives).  Sources are handled as in
        :meth:`bfs`; ``state.improved`` counts the distance decreases.

        Needs a weighted graph (``weights=`` at build time): an
        unweighted one raises ``ValueError``.
        """
        return self._search(SSSPProgram(), sources, policy=policy,
                            max_supersteps=max_supersteps,
                            checkpoint=checkpoint, resume=resume)

    def _search(self, prog: VertexProgram, sources, *, policy,
                max_supersteps, checkpoint, resume) -> ProgramResult:
        """Run a per-source program (one lane per source): a scalar
        source on :func:`run_program` with the lane dropped from
        ``values``, ``[K]`` sources on the batched driver (or on
        :func:`run_program` under a trace)."""
        scalar = np.ndim(sources) == 0
        seeds = jnp.atleast_1d(jnp.asarray(sources, jnp.int32))
        driver = (run_program if scalar or under_trace(seeds)
                  else run_program_batched)
        res = driver(self._sem(policy, prog), prog, policy, seeds=seeds,
                     max_supersteps=max_supersteps,
                     checkpoint=checkpoint, resume=resume)
        return res._replace(values=res.values[:, 0] if scalar else res.values)

    def pagerank(
        self,
        *,
        mode: str = "push",
        damping: float = 0.85,
        tol: float = 1e-3,
        max_iters: int = 100,
        reset=None,
        policy: Optional[ExecutionPolicy] = None,
        checkpoint=None,
        resume: bool = False,
    ) -> ProgramResult:
        """PageRank.  ``values``: f32[n] ranks (sum ≈ 1).

        ``mode='push'`` is Graphyti's delta-push (P1: I/O shrinks as ranks
        converge); ``'pull'`` the Pregel-style baseline it is measured
        against (§4.1, Fig. 2).

        ``reset`` switches to *personalized* PageRank and batches Q
        queries through one engine pass: pass ``int32[Q]`` restart
        vertices (one-hot resets) or a float ``(n, Q)`` matrix of
        per-query reset distributions.  ``values`` becomes ``f32[n, Q]``
        (column q solves query q's fixed point, bitwise-equal to running
        it alone), the result carries ``query_supersteps``, and
        ``iostats.queries == Q``.  Push-only: raise on ``mode='pull'``.
        """
        if mode not in ("push", "pull"):
            raise ValueError(f"unknown pagerank mode {mode!r}")
        if reset is not None:
            if mode != "push":
                raise ValueError(
                    "personalized pagerank (reset=...) is delta-push only; "
                    "drop mode='pull'"
                )
            prog = PersonalizedPageRankProgram(damping=damping, tol=tol)
            seeds = jnp.asarray(reset)
            if seeds.ndim == 0:
                seeds = seeds[None]
            driver = run_program if under_trace(seeds) else run_program_batched
            return driver(self._sem(policy, prog), prog, policy, seeds=seeds,
                          max_supersteps=max_iters,
                          checkpoint=checkpoint, resume=resume)
        prog = (PageRankPushProgram if mode == "push" else PageRankPullProgram)(
            damping=damping, tol=tol
        )
        return run_program(self._sem(policy, prog), prog, policy,
                           max_supersteps=max_iters,
                           checkpoint=checkpoint, resume=resume)

    def coreness(
        self,
        *,
        prune: bool = True,
        messaging: str = "hybrid",
        policy: Optional[ExecutionPolicy] = None,
        max_supersteps: Optional[int] = None,
    ) -> ProgramResult:
        """k-core decomposition (undirected graphs).  ``values``:
        int32[n] core numbers.  ``prune``/``messaging`` keep the Fig. 3
        optimization ladder (P2 + P3)."""
        prog = CorenessProgram(prune=prune, messaging=messaging)
        return run_program(self._sem(policy, prog), prog, policy,
                           max_supersteps=max_supersteps)

    def betweenness(
        self,
        sources=None,
        *,
        mode: str = "multi",
        batch: Optional[int] = None,
        policy: Optional[ExecutionPolicy] = None,
        max_supersteps: Optional[int] = None,
        checkpoint=None,
        resume: bool = False,
    ) -> ProgramResult:
        """Brandes betweenness centrality from K sources.  ``values``:
        f32[n] (un-normalized; exact when ``sources`` is every vertex).

        ``sources`` is required: BC state is O(n · K), so the exact-BC
        choice (``jnp.arange(g.n)`` — O(n²) memory) must be the caller's.

        ``mode``: 'multi' (synchronous multi-source, §4.4), 'uni' (K
        independent runs, the Fig. 6 baseline), or 'fused' (per-source
        phase fusion; ``state.shared`` counts fwd/bwd fetches served by
        one chunk read).  'fused' is a fixed scan-store execution and
        rejects a ``policy``.

        ``batch=Q`` (uni mode only) groups the per-source sweep into
        ceil(K/Q) batched forward/backward passes — every streamed edge
        chunk serves Q sources' sweeps at once, values bitwise-equal to
        the one-source-at-a-time loop; ``iostats.queries`` is stamped K
        so amortized per-query I/O reads off directly."""
        if mode not in ("multi", "uni", "fused"):
            raise ValueError(f"unknown betweenness mode {mode!r}")
        if batch is not None and mode != "uni":
            raise ValueError(
                "betweenness(batch=...) amortizes the per-source uni-mode "
                "sweep; mode='multi' already runs all sources in one pass"
            )
        if sources is None:
            raise ValueError(
                "betweenness() needs explicit sources; pass "
                "jnp.arange(g.n) for exact BC (O(n^2) state) or a sample "
                "of pivots for an estimate"
            )
        sources = jnp.atleast_1d(jnp.asarray(sources, jnp.int32))
        if mode == "fused":
            # Fused BC drives the chunk stores directly (its two-phase
            # shared-fetch accounting has no blocked form); don't accept a
            # policy it would silently ignore, don't build tile views.
            if policy is not None:
                raise ValueError(
                    "betweenness(mode='fused') runs the fixed scan-store "
                    "execution; policy is not supported (use mode='multi')"
                )
            res = run_program(self.device(), FusedBCProgram(), seeds=sources,
                              max_supersteps=max_supersteps,
                              checkpoint=checkpoint, resume=resume)
            return res._replace(values=_finish(res.values, sources))
        sem = self._sem(policy, None, need_reverse=True)
        if mode == "uni":
            bc = jnp.zeros(self.n)
            io = IOStats.zero()
            steps = jnp.zeros((), jnp.int32)
            group = 1 if batch is None else max(int(batch), 1)
            for i in range(0, sources.shape[0], group):
                # per-group checkpoint subtree: a kill mid-sweep resumes
                # at the interrupted group, finished groups replay from
                # their final snapshots.
                ck = checkpoint.child(f"src_{i:05d}") \
                    if checkpoint is not None else None
                b, st, it = _bc_sync(sem, sources[i : i + group],
                                     max_supersteps, policy,
                                     checkpoint=ck, resume=resume)
                bc, io, steps = bc + b, io + st, steps + it
            if batch is not None:
                io = io._replace(queries=_i32(sources.shape[0]))
            return ProgramResult(bc, steps, io)
        bc, io, steps = _bc_sync(sem, sources, max_supersteps, policy,
                                 checkpoint=checkpoint, resume=resume)
        return ProgramResult(bc, steps, io)

    def diameter(
        self,
        *,
        num_sources: int = 32,
        sweeps: int = 2,
        seed_vertex: Optional[int] = None,
        mode: str = "multi",
        policy: Optional[ExecutionPolicy] = None,
    ) -> ProgramResult:
        """Pseudo-peripheral diameter estimate (§4.3).  ``values``: int32
        scalar lower bound on the true diameter (exact on many structured
        graphs).  ``mode='uni'`` is the no-chunk-sharing baseline."""
        if mode not in ("multi", "uni"):
            raise ValueError(f"unknown diameter mode {mode!r}")
        sem = self._sem(policy, BFSProgram())
        est, io, steps = _diameter(sem, policy, num_sources=num_sources,
                                   sweeps=sweeps, seed_vertex=seed_vertex,
                                   multi=(mode == "multi"))
        return ProgramResult(est, steps, io)

    def triangles(
        self,
        *,
        variant: str = "restarted",
        ordered: bool = True,
        hash_threshold: int = 0,
        policy: Optional[ExecutionPolicy] = None,
    ) -> ProgramResult:
        """Triangle count (undirected graphs, §4.5).  ``values``: int
        triangle count; ``state``: the full
        :class:`~repro.algs.TriangleResult` ledger (comparisons, row
        requests) for the host variants.

        A blocked-backend policy routes to the MXU tile path; anything
        else runs the host reference intersections (P6a ladder).
        """
        if (policy is not None and policy.residency == "host"
                and policy.backend in _BLOCKED):
            raise ValueError(
                "triangles with a blocked backend builds the device MXU "
                "tile path (O(m) device bytes); residency='host' has no "
                "streamed form for it — drop the blocked backend (the "
                "reference variants are already host-resident) or use "
                "residency='device'"
            )
        r: TriangleResult = count_triangles(
            self._host, variant=variant, ordered=ordered,
            hash_threshold=hash_threshold, policy=policy,
        )
        return _host_result(
            r.triangles, state=r, requests=r.row_requests, records=r.records,
            bytes_moved=r.records * 8,
        )

    def louvain(
        self,
        *,
        materialize: bool = False,
        max_levels: int = 10,
        max_sweeps: int = 10,
    ) -> ProgramResult:
        """Louvain modularity (undirected graphs, §4.6).  ``values``:
        int community label per vertex; ``state``: the full
        :class:`~repro.algs.LouvainResult` (modularity, levels,
        bytes_written/gather_ops ledger).  The default is the Graphyti
        immutable-edge indirection path (P6b: zero edge bytes rewritten).
        """
        r = _louvain(self._host, materialize=materialize,
                     max_levels=max_levels, max_sweeps=max_sweeps)
        return _host_result(r.comm, supersteps=r.levels, state=r,
                            bytes_moved=r.bytes_written)
