"""True semi-external memory: host-resident edge store + streamed supersteps.

Everything else in the engine *simulates* SEM: the chunk/tile stores are
device-resident, fetch/skip decisions are counted, but every edge byte is
already in device memory — the I/O model is faithful, the residency is not.
This module supplies the missing axis.  A :class:`HostGraph` pins the O(m)
edge arrays in host RAM as plain numpy (:class:`HostChunkStore` /
:class:`HostBlockedStore`, produced by the SAME choppers —
:func:`repro.core.sem.build_store_arrays` and
:func:`repro.kernels.spmv.build_blocked_arrays` — that the device views
wrap, so both residencies stream byte-identical data in the same schedule),
and a streaming executor ships only the live work-list per superstep:

  1. plan on host — the frontier's chunk/tile activity is mirrored in
     numpy (the exact formulas of ``chunk_activity`` / ``tile_activity``),
     yielding the live ids in schedule order;
  2. batch — live units are grouped into ``ExecutionPolicy.stream_buffer``-
     sized staging batches (for the blocked backends, batches additionally
     respect run boundaries; see below);
  3. double-buffer — the batch-k kernel launch is dispatched
     asynchronously, then batch k+1's ``jax.device_put`` runs while it
     computes, so at peak exactly TWO staging buffers are device-resident:
     one computing, one copying.  Peak device bytes are O(n) vertex state
     plus O(stream_buffer) staging — never O(m).

Cost model (the host-link term of :mod:`repro.core.engine`'s docstring): a
superstep pays ``live_bytes / B_link`` transfer overlapped against compute,
so it runs at compute-bound speed whenever ``B_link * t_compute >=
live_bytes`` — the paper's "SEM reaches ~80% of in-memory" regime is
exactly the overlapped case, and activity skipping shrinks ``live_bytes``
with the frontier just as it shrinks SSD reads in FlashGraph.
``IOStats.host_bytes`` is the odometer: the measured ``.nbytes`` of every
``device_put`` payload (padding included); every other order-invariant
IOStats field — and the values — are bitwise-identical across residencies.

Bitwise parity is engineered, not hoped for:

  * scan/compact — live chunks stream in ascending id order across
    batches, the per-chunk fetch is the shared :func:`~repro.core.sem.
    _make_fetch`, and padding slots carry ``valid=False`` (they scatter
    the semiring identity to the sentinel row ``n`` only), so the
    scatter sequence seen by every real row equals the device scan's.
  * blocked — batches NEVER split an accumulator run (rule 1), and a
    destination block already flushed by an earlier batch gets at most
    ONE run per later batch (rule 2), so the host-side cross-batch
    combine ``carry (+)= y_batch`` reproduces the kernel's
    flush-accumulate association exactly.  Within a batch the kernel's
    own ``first``/``last``/``accum`` flags (batch-local) do the work.
  * p2p — the gather plan (active rows ascending, row-major edge order)
    matches the device gather lane-for-lane; extra capacity lanes only
    scatter identities to the sentinel row, which the repo's adaptive-p2p
    parity tests already prove capacity-invariant.

The executors are eager Python (the per-superstep work-list must be
concrete to ship it), so a host-residency traversal cannot run under an
enclosing ``jax.jit`` — :func:`run_program_host` replaces the device
driver's ``lax.while_loop`` with a host loop, jitting the per-superstep
``frontier``/``apply`` hooks (cached per (program-config, policy)) and
keeping gather/activate eager.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.extend.core import jaxpr_as_fun
import numpy as np

from ..graph.csr import Graph
from .engine import (
    ExecutionPolicy,
    PolicyError,
    ResidencyError,
    _blocked_post,
    _blocked_pre_mask,
    _check_blocked_semiring,
    batched_union_frontier,
    beamer_use_pull,
    p2p_caps,
)
from .sem import (
    EDGE_RECORD_BYTES,
    IOStats,
    _make_fetch,
    _pad_y_init,
    _store_record_bytes,
    build_store_arrays,
    flatten_single_lane,
    frontier_edge_mass,
    pad_state,
)
from .semiring import Semiring
from .spans import host_read

__all__ = [
    "HostBlockedStore",
    "HostChunkStore",
    "HostGraph",
    "StreamFailure",
    "host_graph",
    "host_traverse",
    "inject_stream_faults",
    "run_program_host",
]

_BLOCKED = ("blocked", "blocked_compact")


# --------------------------------------------------------------------------
# host-link fault tolerance
# --------------------------------------------------------------------------
class StreamFailure(RuntimeError):
    """A host->device staging batch failed ``stream_retries + 1`` times in
    a row.  Transient link hiccups never surface — the executor retries
    with exponential backoff and counts them in ``IOStats.retries`` — so
    this exception means the link is persistently down."""


# Test-only injection point: a callable invoked once per staging attempt
# (before the device_put batch); raising from it simulates a transient
# host-link failure.  Kept module-global rather than threaded through the
# executors because faults are an ambient property of the link, not of any
# one traversal.
_FAULT_HOOK = None


@contextlib.contextmanager
def inject_stream_faults(hook):
    """Install ``hook()`` to run before every host->device staging batch
    for the duration of the ``with`` block.  A raising hook simulates a
    transient link failure; the executors' bounded retry must absorb it
    (or surface :class:`StreamFailure` once the budget is spent)."""
    global _FAULT_HOOK
    prev = _FAULT_HOOK
    _FAULT_HOOK = hook
    try:
        yield
    finally:
        _FAULT_HOOK = prev


def _staged(pol: ExecutionPolicy, build, units: int):
    """Stage one batch: ``build()`` gathers its host arrays, which then
    ``device_put`` under the policy's bounded retry-with-backoff.  Returns
    ``(device arrays, nbytes, n_retries)``; raises :class:`StreamFailure`
    when ``stream_retries + 1`` attempts all fail.  Retries are safe by
    construction: staging is a pure read of pinned host arrays — no state
    mutates until the shipped payload is used.

    The whole of it is the ``graphyti.stage`` span; its ``bytes`` argument
    is ``nbytes``, the exact count ``IOStats.host_bytes`` adds (padding
    included) before its int32 wrap, and ``units`` the batch's live
    chunks, tiles or edge lanes."""
    with jax.profiler.TraceAnnotation("graphyti.stage", units=units) as span:
        arrays = build()
        nbytes = sum(a.nbytes for a in arrays)
        span.set_metadata(bytes=nbytes)
        attempts = int(pol.stream_retries) + 1
        last = None
        for a in range(attempts):
            try:
                if _FAULT_HOOK is not None:
                    _FAULT_HOOK()
                return tuple(jax.device_put(x) for x in arrays), nbytes, a
            except Exception as e:  # noqa: BLE001 — any staging error is retryable
                last = e
                if a + 1 < attempts and pol.stream_backoff_s > 0:
                    time.sleep(pol.stream_backoff_s * (2 ** a))
    raise StreamFailure(
        f"host->device stream failed after {attempts} attempts "
        f"(stream_retries={pol.stream_retries}): {last!r}"
    ) from last


def _enqueue(kern, *args):
    """Launch one batch kernel (JAX dispatches it asynchronously) under the
    ``graphyti.enqueue`` span."""
    with jax.profiler.TraceAnnotation("graphyti.enqueue"):
        return kern(*args)


def _pow2_at_least(k: int) -> int:
    g = 1
    while g < max(1, k):
        g *= 2
    return g


def _wrap_i32(v) -> jnp.ndarray:
    """Host int -> int32 device scalar with the SAME 2^32 wrap the device
    counters have by contract (int64 accumulate, truncating cast)."""
    return jnp.asarray(np.array(int(v), np.int64).astype(np.int32))


def _loopify(fn):
    """Run ``fn`` inside a single-iteration, eagerly dispatched
    ``lax.while_loop`` so it compiles in the exact codegen context of the
    device driver's BSP loop body (see :meth:`HostGraph._hooks` for why a
    plain ``jax.jit`` is NOT bit-equivalent).  The loop carries the
    arguments so the body is not hoisted as loop-invariant.

    The traced jaxpr is cached per input signature and re-evaluated on
    later calls: a fresh eager ``while_loop`` re-traces per call, and the
    fresh jaxpr object misses the primitive compile cache — ~40ms per
    superstep.  Re-binding the SAME jaxpr is the identical eager dispatch
    path (bit-for-bit) at sub-millisecond cost."""
    cache: dict = {}

    def run(*args):
        out0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(fn, *args)
        )

        def body(carry):
            a, i, _ = carry
            return (a, i + 1, fn(*a))

        return jax.lax.while_loop(lambda c: c[1] < 1, body,
                                  (args, 0, out0))[2]

    def call(*args):
        flat, treedef = jax.tree_util.tree_flatten(args)
        sig = (treedef,
               tuple((jnp.shape(a), jnp.result_type(a)) for a in flat))
        hit = cache.get(sig)
        if hit is None:
            jaxpr, out_shape = jax.make_jaxpr(run, return_shape=True)(*args)
            hit = (jaxpr_as_fun(jaxpr),
                   jax.tree_util.tree_structure(out_shape))
            cache[sig] = hit
        run_jaxpr, out_tree = hit
        return jax.tree_util.tree_unflatten(out_tree, run_jaxpr(*flat))

    return call


# --------------------------------------------------------------------------
# host-pinned stores
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HostChunkStore:
    """:class:`~repro.core.sem.EdgeChunkStore` twin whose arrays are plain
    numpy pinned in host RAM — deliberately NOT a pytree, so no code path
    can silently sweep it onto the device."""

    major: np.ndarray
    minor: np.ndarray
    w: Optional[np.ndarray]
    lo: np.ndarray
    hi: np.ndarray
    n: int
    chunk_size: int
    sorted_by: str

    @property
    def num_chunks(self) -> int:
        return int(self.major.shape[0])

    @property
    def nbytes(self) -> int:
        return int(
            self.major.nbytes + self.minor.nbytes + self.lo.nbytes
            + self.hi.nbytes + (self.w.nbytes if self.w is not None else 0)
        )


@dataclasses.dataclass(frozen=True)
class HostBlockedStore:
    """:class:`~repro.kernels.spmv.BlockedGraph` twin pinned in host RAM
    (same schedule, same run flags; see :func:`build_blocked_arrays`)."""

    tiles: np.ndarray
    dbid: np.ndarray
    sbid: np.ndarray
    first: np.ndarray
    last: np.ndarray
    accum: np.ndarray
    nnz: np.ndarray
    n: int
    bd: int
    bs: int
    semiring: str
    tile_order: str

    @property
    def num_tiles(self) -> int:
        return int(self.tiles.shape[0])

    @property
    def n_dst_blocks(self) -> int:
        return -(-self.n // self.bd)

    @property
    def n_src_blocks(self) -> int:
        return -(-self.n // self.bs)

    @property
    def nbytes(self) -> int:
        return int(sum(
            a.nbytes for a in (self.tiles, self.dbid, self.sbid, self.first,
                               self.last, self.accum, self.nnz)
        ))


class HostGraph:
    """Host-resident SEM view: the ``residency='host'`` twin of
    :class:`~repro.core.sem.SemGraph`.

    Device-resident state is strictly O(n): the degree vectors (the only
    graph arrays the vertex-program hooks read).  Edge data lives in
    numpy stores and is shipped per superstep by the streaming executors;
    ``peak_stage_bytes`` records the largest measured in-flight staging
    footprint (at most two ``stream_buffer`` batches, by construction).
    """

    is_host_view = True

    def __init__(self, host: Graph, *, chunk_size: int = 4096,
                 bd: int = 128, bs: int = 128):
        self.host = host
        self.n = host.n
        self.m = host.m
        self.chunk_size = chunk_size
        self.bd, self.bs = bd, bs
        self.out_store = HostChunkStore(
            **build_store_arrays(host, sorted_by="src", chunk_size=chunk_size)
        )
        has_in = host.in_indptr is not None
        self.in_store = (
            HostChunkStore(**build_store_arrays(host, sorted_by="dst",
                                                chunk_size=chunk_size))
            if has_in else None
        )
        # The one O(n) device footprint (plus transient staging buffers).
        with jax.ensure_compile_time_eval():
            self.out_degree = jnp.asarray(host.out_degree)
            self.in_degree = jnp.asarray(host.in_degree) if has_in else None
        self._blocked: dict = {}  # (semiring, reverse, tile_order) -> store
        self._jit_hooks: dict = {}
        self.peak_stage_bytes = 0

    @property
    def weighted(self) -> bool:
        return self.host.weights is not None

    def __repr__(self) -> str:
        return (f"HostGraph(n={self.n}, m={self.m}, "
                f"chunk_size={self.chunk_size}, "
                f"host_bytes={self.store_nbytes})")

    @property
    def store_nbytes(self) -> int:
        """Total host-pinned edge-store bytes (chunk + tile stores)."""
        total = self.out_store.nbytes
        if self.in_store is not None:
            total += self.in_store.nbytes
        total += sum(s.nbytes for s in self._blocked.values())
        return total

    def blocked_store(self, semiring: str, *, reverse: bool,
                      tile_order: str) -> HostBlockedStore:
        """The host tile store for one (encoding, direction, order) — built
        once per key, exactly like the session's device tile cache."""
        key = (semiring, bool(reverse), tile_order)
        if key not in self._blocked:
            from ..kernels.spmv import build_blocked_arrays

            self._blocked[key] = HostBlockedStore(**build_blocked_arrays(
                self.host, bd=self.bd, bs=self.bs, direction="out",
                semiring=semiring, reverse=reverse, tile_order=tile_order,
            ))
        return self._blocked[key]

    def _note_stage(self, nbytes: int) -> None:
        if nbytes > self.peak_stage_bytes:
            self.peak_stage_bytes = int(nbytes)

    def _hooks(self, prog, pol: ExecutionPolicy):
        """Compiled per-superstep ``frontier``/``apply`` hooks, cached per
        (program type, program config, policy).  ``gather``/``activate``
        stay eager (they call the streaming executors, which must see
        concrete frontiers).

        Each hook is wrapped in a single-iteration *eagerly dispatched*
        ``lax.while_loop`` — NOT a plain ``jax.jit``.  The device driver
        runs these hooks inside its eager ``lax.while_loop`` body, and XLA
        compiles loop bodies more conservatively than straight-line jitted
        code (observed on CPU: ``d*(s/g)`` stays as written in a loop body
        but is reassociated to ``(d*s)/g`` under plain jit — a 1-ulp
        difference that breaks bitwise parity).  Compiling the host hooks
        in the same loop-body context makes them bit-identical."""
        key = (type(prog), tuple(sorted(prog.__dict__.items())), pol)
        hit = self._jit_hooks.get(key)
        if hit is None:
            hit = (
                _loopify(lambda state: prog.frontier(self, state)),
                _loopify(lambda state, gathered:
                         prog.apply(self, state, gathered)),
            )
            self._jit_hooks[key] = hit
        return hit


def host_graph(g: Graph, *, chunk_size: int = 4096, bd: int = 128,
               bs: int = 128) -> HostGraph:
    """Build the host-resident SEM view of ``g`` (the ``residency='host'``
    analogue of :func:`~repro.core.sem.device_graph`).  Chunk stores are
    built eagerly (numpy, no device work); tile stores lazily per
    (encoding, direction, tile_order) on first blocked-backend use."""
    return HostGraph(g, chunk_size=chunk_size, bd=bd, bs=bs)


# --------------------------------------------------------------------------
# compiled per-batch kernels (shape-bucketed, cached)
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _chunk_batch_fn(sr: Semiring, n: int, gather_on_major: bool,
                    has_w: bool):
    """Jitted scan over one staging batch of chunks — the same per-chunk
    fetch (:func:`~repro.core.sem._make_fetch`) the device paths run, so
    each live chunk's scatter is bitwise the device scatter.  ``valid``
    masks padding slots (whole-chunk no-ops).  ``y``/``xp`` arrive as
    :func:`_stream_chunks` leaves them: 1-D for a single-lane state
    (:func:`~repro.core.sem.flatten_single_lane`, which the device scans
    apply too).  Its scan runs under the ``graphyti.chunk_scan`` scope,
    the name of the device residency's chunk scans too."""

    def run(y, msgs, xp, active, major, minor, w, valid):
        fetch = _make_fetch(sr, xp, active, n, gather_on_major, has_w)

        def body(carry, sl):
            y, msgs = carry
            mj, mi, wc, v = sl
            y, mm = fetch(y, mj, mi, wc if has_w else None, v)
            return (y, msgs + mm), None

        with jax.named_scope("graphyti.chunk_scan"):
            (y, msgs), _ = jax.lax.scan(body, (y, msgs),
                                        (major, minor, w, valid))
        return y, msgs

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _tile_batch_fn(semiring: str, n_dst_blocks: int, interpret: bool):
    """Jitted Pallas launch over one staging batch of tiles (the compact
    kernel with batch-local run flags; see :func:`_stream_tiles`)."""
    from ..kernels.spmv.kernel import spmv_pallas_compact

    def run(tiles, perm, dbid, sbid, first, last, accum, nact, x_blocks):
        return spmv_pallas_compact(
            tiles, perm, dbid, sbid, first, last, accum, nact, x_blocks,
            n_dst_blocks, semiring=semiring, interpret=interpret,
        )

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _p2p_tail_fn(sr: Semiring, n: int, has_w: bool, gather_on_major: bool):
    """Jitted device tail of the host p2p path: gather/mask/scatter over
    the shipped edge lanes — op-for-op the tail of
    :func:`~repro.core.sem.p2p_spmv`."""

    def run(y0, xp, major, minor, ew, valid):
        gather_idx = major if gather_on_major else minor
        key = minor if gather_on_major else major
        xv = xp[gather_idx]
        contrib = sr.edge_op(xv, ew if has_w else None)
        if contrib.ndim > 1:
            v2 = valid.reshape((-1,) + (1,) * (contrib.ndim - 1))
        else:
            v2 = valid
        contrib = jnp.where(v2, contrib, jnp.asarray(sr.identity, contrib.dtype))
        key = jnp.where(valid, key, n)
        return sr.scatter(y0, key, contrib)[:n]

    return jax.jit(run)


# --------------------------------------------------------------------------
# streaming executors
# --------------------------------------------------------------------------
def _stream_chunks(hg: HostGraph, store: HostChunkStore, x, active,
                   sr: Semiring, *, reverse: bool, y_init,
                   pol: ExecutionPolicy):
    """The scan/compact backends' host execution: numpy activity plan ->
    ascending live chunk ids -> ``stream_buffer``-sized batches,
    double-buffered host->device.  A single-lane state is flattened here,
    once per superstep, so the batch kernel compiles for a 1-D carry."""
    n, S = store.n, store.chunk_size
    C = store.num_chunks
    gather_on_major = (store.sorted_by == "src") != reverse
    has_w = store.w is not None
    xf, y_init, restore = flatten_single_lane(x, y_init)
    xp = pad_state(xf, sr)
    y = _pad_y_init(sr, xp, y_init, n)
    msgs = jnp.zeros((), jnp.int32)
    B = int(pol.stream_buffer)

    with jax.profiler.TraceAnnotation("graphyti.plan") as span:
        # numpy mirror of chunk_activity: frontier prefix sums over [lo, hi].
        cs = np.cumsum(host_read(active).astype(np.int64))
        prefix = np.concatenate([np.zeros(1, np.int64), cs, cs[-1:]])
        per_chunk = prefix[store.hi + 1] - prefix[store.lo]
        live = np.flatnonzero(per_chunk > 0)
        batches = [live[i:i + B] for i in range(0, len(live), B)]
        span.set_metadata(live=int(live.size), units=C,
                          flat=int(xf.ndim < x.ndim))

    kern = _chunk_batch_fn(sr, n, gather_on_major, has_w)
    # Unweighted stores ship no weight column; the kernel's w operand is a
    # device-side dummy created once (zero host-link traffic).
    w_dummy = None if has_w else jnp.zeros((B, S), jnp.float32)
    host_bytes = 0
    peak = 0
    retr = 0

    def ship(ids):
        nonlocal retr
        k = len(ids)

        def build():
            if k < B:  # last batch: pad with chunk 0, masked whole-chunk
                idx = np.zeros(B, np.int64)
                idx[:k] = ids
            else:
                idx = ids
            valid = np.zeros(B, bool)
            valid[:k] = True
            arrays = (np.ascontiguousarray(store.major[idx]),
                      np.ascontiguousarray(store.minor[idx]), valid)
            if has_w:
                arrays += (np.ascontiguousarray(store.w[idx]),)
            return arrays

        arrays, nb, r = _staged(pol, build, k)
        retr += r
        major, minor, valid = arrays[:3]
        return (major, minor, arrays[3] if has_w else w_dummy, valid), nb

    if batches:
        cur, cur_nb = ship(batches[0])
        for i in range(len(batches)):
            host_bytes += cur_nb
            # async dispatch: the copy below overlaps this batch's compute.
            y_msgs = _enqueue(kern, y, msgs, xp, active, *cur)
            if i + 1 < len(batches):
                nxt, nxt_nb = ship(batches[i + 1])
                peak = max(peak, cur_nb + nxt_nb)
                y, msgs = y_msgs
                cur, cur_nb = nxt, nxt_nb
            else:
                peak = max(peak, cur_nb)
                y, msgs = y_msgs
    hg._note_stage(peak)

    n_live = int(live.size)
    rec = _store_record_bytes(store.w)
    st = IOStats(
        requests=_wrap_i32(int(per_chunk[live].sum())),
        records=_wrap_i32(n_live * S),
        chunks_skipped=_wrap_i32(C - n_live),
        messages=msgs,
        supersteps=jnp.zeros((), jnp.int32),
        bytes_moved=_wrap_i32(n_live * S * rec),
        x_fetches=jnp.zeros((), jnp.int32),
        host_bytes=_wrap_i32(host_bytes),
        retries=_wrap_i32(retr),
    )
    return restore(y[:n]), st


def _tile_encoding(sr: Semiring, weighted: bool) -> str:
    """The session's encoding rule (one source of truth would be nicer,
    but the session cannot be imported here): boolean frontiers ride
    plus_times tiles unless real weights could corrupt the y>0 threshold."""
    if sr.name == "or_and":
        return "bool" if weighted else "plus_times"
    if sr.name == "min_plus":
        return "min_plus"
    return "plus_times"


def _host_select_blocked(hg: HostGraph, direction: str, reverse: bool):
    """(reverse_view?, active_on, major_degree) — the host mirror of
    :func:`~repro.core.engine._select_blocked`."""
    if direction == "out" and not reverse:
        return False, "src", hg.out_degree
    if direction == "out" and reverse:
        return True, "dst", hg.out_degree
    if direction == "in" and not reverse:
        if hg.in_degree is None:
            raise ResidencyError(
                "host graph has no in-edge view; pull ('in') blocked "
                "dispatch needs a graph built with its in-CSR"
            )
        return False, "dst", hg.in_degree
    raise NotImplementedError("blocked backend: direction='in' with reverse")


def _tile_batches(live, run_id, dbid, B: int) -> list:
    """Staging batches of the live tile positions ``live``, as
    ``(positions, dst blocks flushed by the batch)``, under the two rules
    of :func:`_stream_tiles`.  Live runs group consecutive live steps by
    ORIGINAL run id (the same keying compact_tile_order uses, so runs that
    become adjacent when tiles between them go inactive are NOT merged)."""
    if not live.size:
        return []
    lr = run_id[live]
    starts = np.flatnonzero(np.concatenate([[True], lr[1:] != lr[:-1]]))
    ends = np.append(starts[1:], live.size)
    runs = [live[s:e] for s, e in zip(starts, ends)]
    batches = []
    cur, cur_blocks, cur_count = [], set(), 0
    earlier: set = set()
    for r in runs:
        b = int(dbid[r[0]])
        split = cur and (
            cur_count + len(r) > B            # buffer budget
            or (b in earlier and b in cur_blocks)  # rule 2
        )
        if split:
            batches.append((np.concatenate(cur), frozenset(cur_blocks)))
            earlier |= cur_blocks
            cur, cur_blocks, cur_count = [], set(), 0
        cur.append(r)
        cur_blocks.add(b)
        cur_count += len(r)
    batches.append((np.concatenate(cur), frozenset(cur_blocks)))
    return batches


def _stream_tiles(hg: HostGraph, x, active, sr: Semiring, *, direction: str,
                  reverse: bool, y_init, pol: ExecutionPolicy):
    """The blocked backends' host execution.

    Batching must preserve the kernel's float association, so two rules
    govern where a batch may end (both checked against the live schedule's
    run structure):

      rule 1 — a run (maximal live stretch sharing a destination block)
        is never split across batches: within a batch the kernel's own
        zero-init/accumulate/flush reproduces the device grid verbatim;
      rule 2 — once a block has flushed in an earlier batch, at most ONE
        of its runs may appear in any later batch: the host-side combine
        ``carry (+)= y_batch`` then adds exactly one flush per batch in
        schedule order, which is precisely the device kernel's
        ``y = y + acc`` sequence.  (An oversized run becomes its own
        batch — correctness first, buffer budget second.)
    """
    from ..kernels.spmv import default_interpret, tile_byte_size

    use_rev, active_on, deg = _host_select_blocked(hg, direction, reverse)
    store = hg.blocked_store(_tile_encoding(sr, hg.weighted),
                             reverse=use_rev, tile_order=pol.tile_order)
    interpret = pol.interpret if pol.interpret is not None \
        else default_interpret()
    if not interpret and store.tile_order != "dest":
        raise ResidencyError(
            f"tile_order={store.tile_order!r} is only supported in interpret "
            "mode for now (compiled TPU output-window revisits are "
            "unvalidated); use tile_order='dest' or interpret=True"
        )
    boolean = _check_blocked_semiring(sr, store.semiring, hg.weighted)

    n, bd, bs = hg.n, store.bd, store.bs
    nDB, nSB = store.n_dst_blocks, store.n_src_blocks
    xv = _blocked_pre_mask(store.semiring, active_on, active, x, boolean)
    squeeze = xv.ndim == 1
    if squeeze:
        xv = xv[:, None]
    k = xv.shape[1]
    ident = jnp.inf if store.semiring == "min_plus" else 0.0
    xp = jnp.full((nSB * bs, k), ident, xv.dtype).at[:n].set(xv)
    x_blocks = xp.reshape(nSB, bs, k).astype(jnp.float32)

    if active_on == "src":
        blk, nb_blocks, bid = bs, nSB, store.sbid
    else:
        blk, nb_blocks, bid = bd, nDB, store.dbid
    with jax.profiler.TraceAnnotation("graphyti.plan") as span:
        run_id = np.cumsum(store.first) - 1
        # numpy mirror of tile_activity.
        ap = np.zeros(nb_blocks * blk, bool)
        ap[:n] = host_read(active)
        act_blk = ap.reshape(nb_blocks, blk).any(axis=1)
        live = np.flatnonzero(act_blk[bid])
        batches = _tile_batches(live, run_id, store.dbid,
                                int(pol.stream_buffer))
        span.set_metadata(live=int(live.size), units=store.num_tiles)

    ident_out = np.inf if store.semiring == "min_plus" else 0.0
    carry = jnp.full((nDB, bd, k), ident_out, jnp.float32)
    combine = jnp.minimum if store.semiring == "min_plus" \
        else (lambda a, b: a + b)
    host_bytes = 0
    peak = 0
    retr = 0

    if batches:
        kern = _tile_batch_fn(store.semiring, nDB, interpret)

        def build(pos):
            kk = len(pos)
            G = _pow2_at_least(kk)
            tiles = np.zeros((G, bd, bs), np.float32)
            tiles[:kk] = store.tiles[pos]
            # tail steps replay the last live step with first=last=0: no
            # DMA, no compute, no flush (the compact kernel's tail trick).
            perm = np.full(G, kk - 1, np.int32)
            perm[:kk] = np.arange(kk, dtype=np.int32)
            db = store.dbid[pos]
            sb = store.sbid[pos]
            dbid_b = np.full(G, db[-1], np.int32)
            dbid_b[:kk] = db
            sbid_b = np.full(G, sb[-1], np.int32)
            sbid_b[:kk] = sb
            rb = run_id[pos]
            brk = (rb[1:] != rb[:-1]).astype(np.int32)
            first_b = np.zeros(G, np.int32)
            first_b[:kk] = np.concatenate([[1], brk])
            last_b = np.zeros(G, np.int32)
            last_b[:kk] = np.concatenate([brk, [1]])
            # batch-local accum: a run combines iff its block already
            # flushed earlier IN THIS batch (cross-batch combining is the
            # host carry's job).
            accum_b = np.zeros(G, np.int32)
            rstarts = np.flatnonzero(first_b[:kk])
            seen: set = set()
            acc_run = np.zeros(len(rstarts), np.int32)
            for ri, s in enumerate(rstarts):
                blk_id = int(db[s])
                if blk_id in seen:
                    acc_run[ri] = 1
                seen.add(blk_id)
            accum_b[:kk] = acc_run[np.cumsum(first_b[:kk]) - 1]
            nact = np.array([kk], np.int32)
            return (tiles, perm, dbid_b, sbid_b, first_b, last_b, accum_b,
                    nact)

        def ship(pos):
            nonlocal retr
            payload, nb, r = _staged(pol, lambda: build(pos), len(pos))
            retr += r
            return payload, nb

        flushed_before = np.zeros(nDB, bool)
        cur_pay, cur_nb = ship(batches[0][0])
        for i, (_, blocks) in enumerate(batches):
            host_bytes += cur_nb
            y_b = _enqueue(kern, *cur_pay, x_blocks)  # async dispatch
            if i + 1 < len(batches):
                nxt_pay, nxt_nb = ship(batches[i + 1][0])  # overlaps compute
                peak = max(peak, cur_nb + nxt_nb)
            else:
                nxt_pay = None
                peak = max(peak, cur_nb)
            bf = np.zeros(nDB, bool)
            bf[list(blocks)] = True
            fresh = jnp.asarray(bf & ~flushed_before)
            again = jnp.asarray(bf & flushed_before)
            carry = jnp.where(
                fresh[:, None, None], y_b,
                jnp.where(again[:, None, None], combine(carry, y_b), carry),
            )
            flushed_before |= bf
            if nxt_pay is not None:
                cur_pay, cur_nb = nxt_pay, nxt_nb
    hg._note_stage(peak)

    y = carry.reshape(nDB * bd, k)[:n]
    if squeeze:
        y = y[:, 0]
    y = _blocked_post(sr, active_on, active, y, y_init, boolean, x.dtype)

    # ---- IOStats (numpy mirrors of the device formulas) ----
    fetched = int(live.size)
    T = store.num_tiles
    tile_bytes = tile_byte_size(store)
    has_tiles = np.zeros(nb_blocks, bool)
    has_tiles[bid] = True
    per_block_cnt = ap.reshape(nb_blocks, blk).sum(axis=1, dtype=np.int64)
    requests = int(per_block_cnt[has_tiles].sum())
    sb_live = store.sbid[live]
    xf = 0 if fetched == 0 else \
        1 + int(np.count_nonzero(sb_live[1:] != sb_live[:-1]))
    st = IOStats(
        requests=_wrap_i32(requests),
        records=_wrap_i32(fetched * (tile_bytes // EDGE_RECORD_BYTES)),
        chunks_skipped=_wrap_i32(T - fetched),
        messages=frontier_edge_mass(deg, active),
        supersteps=jnp.zeros((), jnp.int32),
        bytes_moved=_wrap_i32(fetched * tile_bytes),
        x_fetches=_wrap_i32(xf),
        host_bytes=_wrap_i32(host_bytes),
        retries=_wrap_i32(retr),
    )
    return y, st


def _host_p2p(hg: HostGraph, x, active, sr: Semiring, *, direction: str,
              y_init, ecap: int, pol: ExecutionPolicy):
    """Point-to-point host path: numpy row-exact gather plan shipped to a
    jitted scatter tail — lane-for-lane the device :func:`p2p_spmv`.

    The lane count is ``ecap``, exactly the device path's static gather
    shape: XLA's scatter-add association can depend on the operand shape,
    so bitwise parity needs identical lanes, not merely identical valid
    lanes (padding lanes only scatter identities to the sentinel row)."""
    n = hg.n
    host = hg.host
    if direction == "out":
        indptr, indices, w = host.indptr, host.indices, host.weights
    else:
        if host.in_indptr is None:
            raise ResidencyError("host graph has no 'in' CSR view")
        indptr, indices, w = host.in_indptr, host.in_indices, host.in_weights
    if hg.m == 0:  # static: no edges, nothing to fetch
        y = sr.neutral_like(pad_state(x, sr), n) if y_init is None else y_init
        return y, IOStats.zero()
    xp = pad_state(x, sr)
    y0 = _pad_y_init(sr, xp, y_init, n)

    with jax.profiler.TraceAnnotation("graphyti.plan") as span:
        act_idx = np.flatnonzero(host_read(active))
        deg = (indptr[act_idx + 1] - indptr[act_idx]).astype(np.int64)
        total = int(deg.sum())
        span.set_metadata(live=len(act_idx), units=n)
    E = int(ecap)
    has_w = w is not None
    # The gate guarantees total <= ecap; mirror the device's lane
    # truncation if it ever doesn't.
    t = min(total, E)

    def build():
        major = np.full(E, n, np.int32)
        minor = np.full(E, n, np.int32)
        ew = np.zeros(E, np.float32) if has_w else None
        valid = np.zeros(E, bool)
        if t:
            offs = np.cumsum(deg)
            row_start = offs - deg
            p = np.arange(t, dtype=np.int64)
            kix = np.searchsorted(offs, p, side="right")
            e = indptr[act_idx[kix]].astype(np.int64) + (p - row_start[kix])
            major[:t] = np.repeat(act_idx.astype(np.int32), deg)[:t]
            minor[:t] = np.asarray(indices)[e].astype(np.int32)
            if has_w:
                ew[:t] = np.asarray(w, np.float32)[e]
            valid[:t] = True
        return (major, minor, valid) + ((ew,) if has_w else ())

    arrays, nb, retr = _staged(pol, build, t)
    hg._note_stage(nb)
    dm, dn, dv = arrays[:3]
    dw = arrays[3] if has_w else dv  # dw: unused operand when not has_w
    run = _p2p_tail_fn(sr, n, has_w, direction == "out")
    y = _enqueue(run, y0, xp, dm, dn, dw, dv)

    rec = _store_record_bytes(w)
    st = IOStats(
        requests=_wrap_i32(len(act_idx)),
        records=_wrap_i32(total),
        chunks_skipped=jnp.zeros((), jnp.int32),
        messages=_wrap_i32(total),
        supersteps=jnp.zeros((), jnp.int32),
        bytes_moved=_wrap_i32(total * rec),
        x_fetches=jnp.zeros((), jnp.int32),
        host_bytes=_wrap_i32(nb),
        retries=_wrap_i32(retr),
    )
    return y, st


# --------------------------------------------------------------------------
# dispatch + traverse (the engine's control flow, decisions forced concrete)
# --------------------------------------------------------------------------
def _host_multicast(hg, x, active, sr, *, direction, reverse, y_init, pol):
    """Multicast arm: the host always streams exactly the live work-list,
    which is value- and stats-identical to both the device dense and
    compact arms (the dense/compact lax.cond exists for wall-clock, not
    accounting), so no density split is needed here."""
    if pol.backend in _BLOCKED:
        return _stream_tiles(hg, x, active, sr, direction=direction,
                             reverse=reverse, y_init=y_init, pol=pol)
    if pol.backend not in ("scan", "compact"):
        raise PolicyError(f"unknown backend {pol.backend!r}")
    store = hg.out_store if direction == "out" else hg.in_store
    if store is None:
        raise ResidencyError(f"host graph has no {direction!r} store")
    return _stream_chunks(hg, store, x, active, sr, reverse=reverse,
                          y_init=y_init, pol=pol)


def _host_dispatch(hg, x, active, sr, *, direction, reverse, y_init, pol):
    """The density three-way for one direction, with the p2p gate computed
    by the SAME device formula as :func:`~repro.core.engine._dispatch`
    (then forced concrete) so both residencies choose identically."""
    if pol.switch_fraction is None or reverse:
        return _host_multicast(hg, x, active, sr, direction=direction,
                               reverse=reverse, y_init=y_init, pol=pol)
    deg = hg.out_degree if direction == "out" else hg.in_degree
    if deg is None:  # no in view: let the multicast arm raise its error
        return _host_multicast(hg, x, active, sr, direction=direction,
                               reverse=reverse, y_init=y_init, pol=pol)
    vcap, ecap, gate = p2p_caps(pol, hg.n, hg.m)
    with jax.profiler.TraceAnnotation("graphyti.plan"):
        act_edges = frontier_edge_mass(deg, active)
        n_act = jnp.sum(active.astype(jnp.int32))
        use_p2p = bool(host_read(
            (act_edges <= jnp.int32(gate))
            & (act_edges <= ecap)
            & (n_act <= vcap)
        ))
    if use_p2p:
        return _host_p2p(hg, x, active, sr, direction=direction,
                         y_init=y_init, ecap=ecap, pol=pol)
    return _host_multicast(hg, x, active, sr, direction=direction,
                           reverse=reverse, y_init=y_init, pol=pol)


def _host_pull_available(hg: HostGraph, pol: ExecutionPolicy) -> bool:
    """Host mirror of :func:`~repro.core.engine._pull_available` (the
    blocked tile view is always buildable here — it streams the forward
    tiles, which need only the out-CSR the host store always has)."""
    if hg.in_degree is None:
        return False
    if pol.backend not in _BLOCKED and hg.in_store is None:
        return False
    if pol.switch_fraction is not None and hg.host.in_indptr is None:
        return False
    return True


def host_traverse(
    hg: HostGraph,
    x,
    active,
    sr: Semiring,
    *,
    policy: Optional[ExecutionPolicy] = None,
    unexplored=None,
    reverse: bool = False,
    y_init=None,
):
    """One streamed superstep on a host-resident graph — the
    ``residency='host'`` execution of :func:`~repro.core.engine.traverse`,
    with identical dispatch structure and identical results/IOStats
    (``host_bytes`` aside).  Must run eagerly: the live work-list is
    planned on host, so a traced frontier cannot be streamed."""
    pol = policy if policy is not None else ExecutionPolicy(residency="host")
    if isinstance(x, jax.core.Tracer) or isinstance(active, jax.core.Tracer):
        raise ValueError(
            "residency='host' streaming cannot run under jit: the executor "
            "plans each superstep's host->device copies from the concrete "
            "frontier.  Drive it through run_program / repro.Graph (the "
            "host BSP driver keeps the loop eager and jits the per-step "
            "hooks instead)"
        )
    if active.ndim > 1:
        # Batched query lanes: stream the union of the per-query frontiers
        # once (this is where the host-link amortization is realized — one
        # double-buffered tile/chunk upload serves all Q live queries),
        # with each lane's x identity-masked by its own frontier.  Shares
        # the engine's helper so both residencies batch identically.
        xm, union, un_union, mass = batched_union_frontier(
            hg, x, active, sr, unexplored=unexplored, reverse=reverse,
            direction=pol.direction,
        )
        y, st = host_traverse(hg, xm, union, sr, policy=pol,
                              unexplored=un_union, reverse=reverse,
                              y_init=y_init)
        return y, st._replace(messages=mass)
    if reverse or unexplored is None:
        direction = pol.direction if pol.direction in ("out", "in") else "out"
        return _host_dispatch(hg, x, active, sr, direction=direction,
                              reverse=reverse, y_init=y_init, pol=pol)

    mf = frontier_edge_mass(hg.out_degree, active)
    mode = pol.direction
    if mode != "out" and not _host_pull_available(hg, pol):
        if mode == "in":
            raise ResidencyError(
                "direction='in' needs the graph's pull views (in-store / "
                "in_degree; blocked backends also need the forward tile "
                "view) — build the graph with its in-CSR"
            )
        mode = "out"  # 'auto' without pull views: push is the only option

    if mode == "out":
        y, st = _host_dispatch(hg, x, active, sr, direction="out",
                               reverse=False, y_init=y_init, pol=pol)
        return y, st._replace(messages=mf)

    mask = active.reshape((-1,) + (1,) * (x.ndim - 1))
    xm = jnp.where(mask, x, jnp.asarray(sr.identity, x.dtype))
    if mode == "in":
        y, st = _host_dispatch(hg, xm, unexplored, sr, direction="in",
                               reverse=False, y_init=y_init, pol=pol)
        return y, st._replace(messages=mf)

    use_pull = bool(host_read(beamer_use_pull(
        mf,
        frontier_edge_mass(hg.out_degree, unexplored),
        jnp.sum(active.astype(jnp.int32)),
        hg.n,
        alpha=pol.alpha,
        beta=pol.beta,
    )))
    if use_pull:
        y, st = _host_dispatch(hg, xm, unexplored, sr, direction="in",
                               reverse=False, y_init=y_init, pol=pol)
    else:
        y, st = _host_dispatch(hg, x, active, sr, direction="out",
                               reverse=False, y_init=y_init, pol=pol)
    return y, st._replace(messages=mf)


# --------------------------------------------------------------------------
# the host BSP driver
# --------------------------------------------------------------------------
def run_program_host(
    sg,
    prog,
    policy: Optional[ExecutionPolicy] = None,
    *,
    seeds=None,
    max_supersteps: Optional[int] = None,
    checkpoint=None,
    resume: bool = False,
    _plan=None,
):
    """:func:`~repro.core.program.run_program`'s host-residency twin: the
    same superstep body, but as an eager Python loop (each superstep must
    plan its streaming batches from a concrete frontier).  ``frontier`` /
    ``apply`` run jitted (cached per program config + policy);
    ``gather``/``activate`` run eager so their traverse calls hit the
    streaming executors.  Supersteps, values, and all order-invariant
    IOStats fields match the device driver's ``lax.while_loop`` exactly.

    ``checkpoint`` / ``resume`` / ``_plan`` mirror the checkpointed device
    driver (see :mod:`repro.core.recovery`): the loop is already eager, so
    snapshots drop in at superstep boundaries with no driver surgery —
    resume-exactness (values AND the full IOStats ledger, ``host_bytes``
    and ``retries`` included) follows because the accumulated ledger is
    part of the snapshot."""
    if not getattr(sg, "is_host_view", False):
        raise ResidencyError(
            "residency='host' policy met a device-resident graph: this "
            "SemGraph's edge store already lives in device memory, so "
            "streaming it from host would misreport residency.  Run "
            "through repro.Graph (sessions key views on residency) or "
            "build a host view with repro.core.residency.host_graph()"
        )
    pol = policy if policy is not None else prog.default_policy
    pol = pol if pol is not None else ExecutionPolicy()
    if pol.residency != "host":
        raise ResidencyError(
            "device-residency policy met a host-resident graph view: its "
            "edge store has no device copy to dispatch on.  Use "
            "ExecutionPolicy(residency='host') or build a device view "
            "with device_graph()"
        )
    pol = prog.prepare_policy(sg, pol)
    state = prog.init(sg, seeds)
    budget = int(max_supersteps if max_supersteps is not None
                 else prog.max_supersteps(sg))
    frontier_fn, apply_fn = sg._hooks(prog, pol)

    from .program import ProgramResult

    ctx = None
    if checkpoint is not None:
        from .recovery import _CheckpointCtx, run_fingerprint

        ctx = _CheckpointCtx(checkpoint,
                             run_fingerprint(sg, prog, pol, seeds))

    io = IOStats.zero()
    it = 0
    done = bool(host_read(prog.converged(sg, state, None))) \
        if prog.check_initial_convergence else False
    if resume and ctx is not None:
        hit = ctx.try_restore(sg, state)
        if hit is not None:
            state, io, it, finished = hit
            if finished:
                return ProgramResult(prog.finalize(sg, state),
                                     jnp.asarray(it, jnp.int32), io, state)
            done = False  # an unfinished snapshot is mid-loop by definition

    from .recovery import maybe_fail

    try:
        while not done and it < budget:
            maybe_fail(_plan, it)
            with jax.profiler.TraceAnnotation("graphyti.superstep", it=it):
                fr = frontier_fn(state)
                gathered, st = prog.gather(sg, state, fr, pol)
                state, activated = apply_fn(state, gathered)
                state, st_act = prog.activate(sg, state, pol)
                io = io + st
                if st_act is not None:
                    io = io + st_act
                io = io._replace(supersteps=io.supersteps + 1)
                done = bool(host_read(prog.converged(sg, state, activated)))
            it += 1
            finished = done or it >= budget
            if ctx is not None and ctx.due(it, finished):
                act = frontier_fn(state).active
                if act.ndim > 1:  # batched lanes: snapshot the 1-D union
                    act = jnp.any(act, axis=-1)
                ctx.save(it, finished, state, io, act)
    except BaseException:
        if ctx is not None:
            ctx.wait()  # drain any in-flight async save before unwinding
        raise
    if ctx is not None:
        if it == 0:  # zero-superstep runs still leave a restorable record
            ctx.save(0, True, state, io, jnp.zeros(sg.n, bool))
        ctx.wait()

    return ProgramResult(prog.finalize(sg, state), jnp.asarray(it, jnp.int32),
                         io, state)
