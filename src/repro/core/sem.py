"""Semi-external-memory edge store: blocked, streamable, skippable.

This is the TPU adaptation of FlashGraph's SAFS-backed edge storage.

The paper's model:   O(n) vertex state in DRAM, O(m) edge lists on SSD,
                     selective async page reads for active vertices.
This module's model: O(n) dense vertex-state vectors resident in fast memory,
                     O(m) edge records laid out in fixed-size *chunks* sorted
                     by a major vertex, streamed through the compute unit with
                     **chunk-activity skipping** — a chunk is fetched only if
                     the frontier intersects its contiguous major-vertex range.

Every fetch/skip decision is counted (`IOStats`), which is what lets the
chip benchmark (``python3 bench/run.py``) report the I/O the paper's
figures plot (Fig. 2, 5, 6) rather than just its algorithm outputs.

Layouts:
  * ``sorted_by='src'`` — *push* store. Active sources send contributions
    along out-edges; output is a scatter-combine keyed by dst.
  * ``sorted_by='dst'`` — *pull* store. Active destinations gather from all
    in-edges; chunk skipping keys on dst activity.

Both are consumed by :func:`sem_spmv` (chunked, skipping, counted — the SEM
path) and by :func:`repro.core.engine.flat_spmv` (the in-memory baseline).
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.csr import Graph
from .semiring import Semiring

__all__ = [
    "EDGE_RECORD_BYTES",
    "IOStats",
    "EdgeChunkStore",
    "SemGraph",
    "bucket_index",
    "build_store",
    "build_store_arrays",
    "chunk_activity",
    "compact_spmv",
    "device_graph",
    "flatten_single_lane",
    "frontier_edge_mass",
    "pad_state",
    "pow2_buckets",
    "sem_spmv",
    "p2p_spmv",
]

# One edge record = (major:int32, minor:int32). Weighted stores add 4 bytes.
EDGE_RECORD_BYTES = 8


def _store_record_bytes(w) -> int:
    """On-disk bytes per edge record for a store/row layout: 8 for the
    (major, minor) int32 pair, +4 when a float32 weight rides along."""
    return EDGE_RECORD_BYTES + (4 if w is not None else 0)


class IOStats(NamedTuple):
    """I/O accounting, in *records* plus layout-aware real bytes.

    requests: per-vertex edge-list I/O requests issued — FlashGraph/SAFS
      issues one request per active vertex row; the page cache then
      coalesces overlapping reads.  The paper's "I/O requests" metric.
    records: edge records actually transferred after coalescing (whole
      chunks for the multicast path, exact rows for point-to-point).
    chunks_skipped: chunks whose fetch was elided by activity skipping.
    messages: edge contributions combined (the paper's message count).
    supersteps: BSP iterations executed.
    bytes_moved: bytes actually transferred, charged by each path's real
      layout — 8 B/record for unweighted chunk/row fetches, 12 B/record
      for weighted stores, 4 B/slot for dense f32 tiles, and 1 bit/slot
      for ``bool`` occupancy tiles (shipped as bitmaps).  This is what
      makes the SEM-vs-in-memory claim a *bytes* claim, not a slot count.
    x_fetches: vertex-state (x) block DMAs issued by the blocked Pallas
      backends' live tile schedule — the counter
      ``ExecutionPolicy.tile_order`` exists to minimize (a Hilbert/Morton
      schedule reuses the resident x window across consecutive tiles; the
      destination-sorted schedule re-fetches it once per destination row).
      Zero on the scan/compact/p2p paths, which charge their x reads into
      ``records``/``bytes_moved`` row-exactly.  Unlike every other field
      it is schedule-SENSITIVE: two policies differing only in
      ``tile_order`` report identical requests/records/bytes and differ
      here alone.
    host_bytes: *measured* bytes shipped across the host->device link by
      the ``residency='host'`` streaming executor (the ``.nbytes`` of every
      ``jax.device_put`` payload, batch padding included) — this is the one
      counter that is an odometer rather than a model.  Zero on every
      device-resident path, so it is residency-SENSITIVE by construction:
      host and device runs of the same policy agree on every other
      order-invariant field and differ here alone, which is why the
      host-vs-device parity checks exclude it.
    retries: transient host->device transfer failures absorbed by the
      ``residency='host'`` streaming path's bounded retry-with-backoff
      (``ExecutionPolicy.stream_retries``) — the observable cost of
      recovery.  Zero on every device-resident path and on any fault-free
      host run, so like ``host_bytes`` it is excluded from cross-residency
      parity checks (a retried batch re-ships the same bytes and produces
      the same values; only this odometer moves).
    queries: number of concurrent query columns (Q) the run's traversals
      were amortized across — stamped once at exit by the batched
      multi-source driver (:func:`repro.core.run_program_batched`), 0 on
      every unbatched run.  Not an accumulating counter: divide any other
      field by ``max(queries, 1)`` for the per-query amortized cost (e.g.
      ``host_bytes / queries`` is the host-link bytes each query paid —
      the number batching exists to shrink).

    All counters are int32 (JAX's default integer without x64), so each
    wraps at 2^31 of its unit — ~2 GiB for ``bytes_moved``, ~2.1e9 edge
    contributions for ``messages``.  Ample for the bench/CI workloads;
    paper-scale runs that could exceed a counter should drain per-superstep
    deltas host-side instead of accumulating one IOStats across the run.
    """

    requests: jnp.ndarray
    records: jnp.ndarray
    chunks_skipped: jnp.ndarray
    messages: jnp.ndarray
    supersteps: jnp.ndarray
    bytes_moved: jnp.ndarray
    x_fetches: jnp.ndarray
    host_bytes: jnp.ndarray
    retries: jnp.ndarray = 0
    queries: jnp.ndarray = 0

    @staticmethod
    def zero() -> "IOStats":
        z = jnp.zeros((), dtype=jnp.int32)
        return IOStats(z, z, z, z, z, z, z, z, z, z)

    def __add__(self, other: "IOStats") -> "IOStats":  # type: ignore[override]
        return IOStats(*(a + b for a, b in zip(self, other)))

    def bytes(self, weighted: Optional[bool] = None) -> int:
        """Layout-aware bytes moved.  ``weighted`` is deprecated and
        ignored — each execution path now charges its own record layout
        into ``bytes_moved`` at the point of transfer."""
        return int(self.bytes_moved)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeChunkStore:
    """Fixed-size edge chunks sorted by a major vertex.

    Data fields (jnp arrays):
      major: int32[C, S] — sort-major endpoint (src for push, dst for pull);
        padding entries hold the sentinel ``n``.
      minor: int32[C, S] — the other endpoint; padding holds ``n``.
      w: optional float32[C, S] edge weights.
      lo, hi: int32[C] — inclusive major-vertex range covered by each chunk
        (``lo == hi == n`` for all-padding chunks). Ranges are contiguous
        because edges are sorted, which is what makes activity testing O(1)
        per chunk via a frontier prefix sum.
    """

    major: jnp.ndarray
    minor: jnp.ndarray
    w: Optional[jnp.ndarray]
    lo: jnp.ndarray
    hi: jnp.ndarray
    n: int = dataclasses.field(metadata=dict(static=True))
    chunk_size: int = dataclasses.field(metadata=dict(static=True))
    sorted_by: str = dataclasses.field(metadata=dict(static=True))

    @property
    def num_chunks(self) -> int:
        return int(self.major.shape[0])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SemGraph:
    """Device-resident SEM view of a graph.

    ``out_store``/``in_store`` are the push/pull chunk stores. ``indptr`` /
    ``indices`` (CSR, out-edges) back the point-to-point path; ``in_indptr``
    / ``in_indices`` likewise for in-edges. ``indptr`` is padded to length
    n+2 so the sentinel vertex ``n`` has a valid empty row.

    ``out_blocked``/``out_blocked_rev`` are the optional dense-tile views
    that back the ``backend='blocked'`` Pallas path of the engine (see
    :mod:`repro.kernels.spmv`): ``out_blocked`` holds the forward operator
    y[dst] (+)= x[src] (serving push with source-block skipping AND pull
    with destination-block skipping); ``out_blocked_rev`` holds its
    transpose y[src] (+)= x[dst] for reverse flows (betweenness backward).
    Built only when ``device_graph(..., blocked=True)`` — the tiles are
    dense, so this trades O(T * Bd * Bs) memory for MXU streaming.
    """

    out_store: Optional[EdgeChunkStore]
    in_store: Optional[EdgeChunkStore]
    indptr: jnp.ndarray
    indices: jnp.ndarray
    w: Optional[jnp.ndarray]
    in_indptr: Optional[jnp.ndarray]
    in_indices: Optional[jnp.ndarray]
    in_w: Optional[jnp.ndarray]
    out_degree: jnp.ndarray
    in_degree: Optional[jnp.ndarray]
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    out_blocked: Optional[object] = None  # kernels.spmv.BlockedGraph
    out_blocked_rev: Optional[object] = None


def build_store_arrays(
    g: Graph, *, sorted_by: str, chunk_size: int = 4096
) -> dict:
    """Numpy core of :func:`build_store`: chop a CSR/CSC view into
    fixed-size streamable chunks, returning plain host arrays.

    The ``residency='host'`` path keeps exactly these arrays pinned in host
    RAM (:class:`repro.core.residency.HostChunkStore`) and ships slices on
    demand, while :func:`build_store` wraps them as device arrays — the one
    chopper guarantees both residencies stream byte-identical chunks.
    """
    assert sorted_by in ("src", "dst")
    if sorted_by == "src":
        indptr, minor, w = g.indptr, g.indices, g.weights
    else:
        if g.in_indptr is None:
            raise ValueError("graph lacks the in-edge view needed for a pull store")
        indptr, minor, w = g.in_indptr, g.in_indices, g.in_weights
    n, m = g.n, int(minor.shape[0])
    major = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))

    num_chunks = max(1, -(-m // chunk_size))
    pad = num_chunks * chunk_size - m
    majp = np.concatenate([major, np.full(pad, n, np.int32)]).reshape(
        num_chunks, chunk_size
    )
    minp = np.concatenate([minor.astype(np.int32), np.full(pad, n, np.int32)]).reshape(
        num_chunks, chunk_size
    )
    wp = None
    if w is not None:
        wp = np.concatenate([np.asarray(w, np.float32), np.zeros(pad, np.float32)]
                            ).reshape(num_chunks, chunk_size)
    valid = majp < n
    any_valid = valid.any(axis=1)
    lo = np.where(any_valid, majp.min(axis=1, where=valid, initial=n), n)
    hi = np.where(any_valid, majp.max(axis=1, where=valid, initial=-1), n)
    return dict(
        major=majp,
        minor=minp,
        w=wp,
        lo=lo.astype(np.int32),
        hi=hi.astype(np.int32),
        n=n,
        chunk_size=chunk_size,
        sorted_by=sorted_by,
    )


def build_store(
    g: Graph, *, sorted_by: str, chunk_size: int = 4096
) -> EdgeChunkStore:
    """Chop a CSR/CSC view into fixed-size streamable chunks (host side)."""
    a = build_store_arrays(g, sorted_by=sorted_by, chunk_size=chunk_size)
    return EdgeChunkStore(
        major=jnp.asarray(a["major"]),
        minor=jnp.asarray(a["minor"]),
        w=None if a["w"] is None else jnp.asarray(a["w"]),
        lo=jnp.asarray(a["lo"]),
        hi=jnp.asarray(a["hi"]),
        n=a["n"],
        chunk_size=a["chunk_size"],
        sorted_by=a["sorted_by"],
    )


def device_graph(
    g: Graph,
    *,
    chunk_size: int = 4096,
    pull: bool = True,
    push: bool = True,
    blocked: bool = False,
    blocked_reverse: bool = False,
    bd: int = 128,
    bs: int = 128,
    blocked_semiring: str = "plus_times",
    tile_order: str = "dest",
) -> SemGraph:
    """Build the full device-resident SEM view of ``g``.

    ``blocked=True`` additionally builds the dense-tile forward operator
    view consumed by the engine's ``backend='blocked'`` Pallas path
    (``bd``/``bs`` are the tile dims, ``blocked_semiring`` the tile
    encoding — 'plus_times' also serves boolean or_and frontiers; use
    'bool' occupancy tiles for exact or_and on weighted graphs, 'min_plus'
    for shortest-path semirings).  ``blocked_reverse=True`` also builds the
    transposed view needed by reverse flows (betweenness backward) — off by
    default since it doubles the dense-tile footprint.  ``tile_order``
    ('dest' | 'morton' | 'hilbert') picks the tiles' streaming schedule and
    must match the :class:`~repro.core.engine.ExecutionPolicy.tile_order`
    of the policies run against the view (``repro.Graph`` sessions key
    their tile cache by it and handle this automatically).
    """

    def _pad_indptr(ip: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(np.concatenate([ip, ip[-1:]]).astype(np.int32))

    out_blocked = out_blocked_rev = None
    if blocked:
        from ..kernels.spmv import build_blocked

        out_blocked = build_blocked(
            g, bd=bd, bs=bs, direction="out", semiring=blocked_semiring,
            tile_order=tile_order,
        )
        if blocked_reverse:
            out_blocked_rev = build_blocked(
                g, bd=bd, bs=bs, direction="out", semiring=blocked_semiring,
                reverse=True, tile_order=tile_order,
            )

    has_in = g.in_indptr is not None
    return SemGraph(
        out_store=build_store(g, sorted_by="src", chunk_size=chunk_size)
        if push
        else None,
        in_store=build_store(g, sorted_by="dst", chunk_size=chunk_size)
        if (pull and has_in)
        else None,
        indptr=_pad_indptr(g.indptr),
        indices=jnp.asarray(g.indices),
        w=None if g.weights is None else jnp.asarray(g.weights),
        in_indptr=_pad_indptr(g.in_indptr) if has_in else None,
        in_indices=jnp.asarray(g.in_indices) if has_in else None,
        in_w=None if (not has_in or g.in_weights is None) else jnp.asarray(g.in_weights),
        out_degree=jnp.asarray(g.out_degree),
        in_degree=jnp.asarray(g.in_degree) if has_in else None,
        n=g.n,
        m=g.m,
        out_blocked=out_blocked,
        out_blocked_rev=out_blocked_rev,
    )


def pad_state(x: jnp.ndarray, sr: Semiring) -> jnp.ndarray:
    """Append the sentinel row ``n`` holding the semiring identity."""
    pad_row = jnp.full((1,) + x.shape[1:], sr.identity, dtype=x.dtype)
    return jnp.concatenate([x, pad_row], axis=0)


def flatten_single_lane(x: jnp.ndarray, y_init: Optional[jnp.ndarray] = None):
    """Carry a state with exactly one trailing lane as a 1-D vector.

    A ``[n, 1]`` state (one BFS key, one query column) and the ``[n]``
    state PageRank keeps hold the same numbers, but the TPU tiles
    ``[n + 1, 1]`` unlike the ``[n + 1]`` its scatter wants: a chunk scan
    carrying the 2-D form relayouts the whole carry (and the gathered
    ``x``) on every chunk step.  The chunk scans therefore flatten such a
    state once per call, run the 1-D scan, and restore the shape once on
    exit.  The decision rests on ``x``'s shape alone: a state with no
    trailing axis or with more than one lane passes through unchanged.

    Returns ``(x, y_init, restore)``: ``x`` and ``y_init`` flattened to
    ``[n]`` where ``x.shape[1:]`` has product 1, and ``restore`` mapping a
    ``[rows]`` result back to ``[rows] + x.shape[1:]`` (the identity
    otherwise).  Host and device residencies both go through here, so
    both scan with the same per-chunk fetch, bitwise.
    """
    lanes = x.shape[1:]
    if not lanes or math.prod(lanes) != 1:
        return x, y_init, lambda y: y

    def flat(a):
        return None if a is None else a.reshape(a.shape[0])

    return flat(x), flat(y_init), lambda y: y.reshape(y.shape[:1] + lanes)


def _active_prefix(active: jnp.ndarray) -> jnp.ndarray:
    """prefix[i] = #active in [0, i); length n+2 so sentinel hi=n is safe."""
    c = jnp.cumsum(active.astype(jnp.int32))
    return jnp.concatenate([jnp.zeros(1, jnp.int32), c, c[-1:]])


def chunk_activity(store: EdgeChunkStore, active: jnp.ndarray) -> jnp.ndarray:
    """bool[C]: which chunks the frontier would fetch.

    Works identically on push (sorted_by='src') and pull (sorted_by='dst')
    stores — the activity vector is always over the store's *major* vertex,
    so the engine's direction-optimizing dispatch calls this with the
    frontier for the push store and with the unexplored/candidate set for
    the pull store.  Also used by fused-phase algorithms (betweenness §4.4)
    to account for chunk fetches *shared* between concurrent phases — the
    analogue of FlashGraph page-cache hits when multiple searches touch the
    same page in one superstep.
    """
    prefix = _active_prefix(active)
    return (prefix[store.hi + 1] - prefix[store.lo]) > 0


def frontier_edge_mass(degree: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """int32 scalar: total degree over the active set.

    The quantity both switch heuristics key on — Beamer's push/pull flip
    compares the frontier's out-edge mass against the unexplored mass, and
    the p2p switch compares it against ``switch_fraction * m``.

    ``active`` may carry trailing query lanes (bool[n, Q]): the mass is
    then summed over every live (vertex, lane) pair, i.e. the total edge
    contributions a batched superstep combines across all Q queries.
    """
    deg = degree.reshape(degree.shape + (1,) * (active.ndim - degree.ndim))
    return jnp.sum(jnp.where(active, deg, 0)).astype(jnp.int32)


def pow2_buckets(cap: int) -> tuple:
    """(1, 2, 4, ..., cap): the compiled work-list capacities.

    Only ``log2(cap) + 1`` distinct sizes exist, so tracing one compact
    scan per bucket is cheap while a draining frontier runs on the
    smallest bucket that fits it.
    """
    out, c = [], 1
    while c < cap:
        out.append(c)
        c *= 2
    out.append(int(max(1, cap)))
    return tuple(out)


def bucket_index(count: jnp.ndarray, buckets: tuple) -> jnp.ndarray:
    """Index of the smallest bucket >= ``count`` (device-side, no host
    round-trip — this is what lets the engine pick a pow2 work-list size
    per superstep inside a jitted BSP loop via ``lax.switch``)."""
    edges = jnp.asarray(buckets[:-1], jnp.int32)
    return jnp.sum((count > edges).astype(jnp.int32))


def _make_fetch(sr, xp, active, n, gather_on_major, has_w):
    """One chunk's worth of the SEM hot loop: gather, mask, scatter-combine.

    Returns ``fetch(y, major, minor, w, step_valid=None) -> (y, messages)``;
    ``step_valid`` additionally masks the whole chunk (used by the compact
    path for work-list slots past the live count).
    """

    def fetch(y, major, minor, w, step_valid=None):
        gather_idx = major if gather_on_major else minor
        key = minor if gather_on_major else major
        xv = xp[gather_idx]
        mask = active[jnp.minimum(major, n - 1)] & (major < n)
        if step_valid is not None:
            mask = mask & step_valid
        contrib = sr.edge_op(xv, w if has_w else None)
        if contrib.ndim > 1:
            m2 = mask.reshape((-1,) + (1,) * (contrib.ndim - 1))
        else:
            m2 = mask
        contrib = jnp.where(m2, contrib, jnp.asarray(sr.identity, contrib.dtype))
        key = jnp.where(mask, key, n)  # sentinel bucket for masked lanes
        y = sr.scatter(y, key, contrib)
        return y, jnp.sum(mask.astype(jnp.int32))

    return fetch


def _pad_y_init(sr, xp, y_init, n):
    if y_init is None:
        return sr.neutral_like(xp, n + 1)
    return jnp.concatenate(
        [y_init, jnp.full((1,) + y_init.shape[1:], sr.identity, y_init.dtype)], 0
    )


def sem_spmv(
    store: EdgeChunkStore,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    y_init: Optional[jnp.ndarray] = None,
    *,
    reverse: bool = False,
) -> tuple[jnp.ndarray, IOStats]:
    """Streamed, chunk-skipping semiring SpMV — the SEM hot loop.

    Computes, over every edge whose **major** endpoint is active,
    ``y[key] = combine(y[key], edge_op(x[gather], w))`` where for a push
    store (sorted_by='src') gather=src=major, key=dst=minor, and for a pull
    store (sorted_by='dst') gather=src=minor, key=dst=major.

    ``reverse=True`` swaps gather/key (messages flow against the store's
    natural direction) while keeping the activity mask on the major vertex —
    e.g. betweenness backward propagation pulls successor values onto active
    predecessors through the same out-edge chunks the forward pass pushed
    through.

    Args:
      x: float/bool[n, ...] vertex state (unpadded; padded internally;
        one trailing lane scans as ``[n]``, see :func:`flatten_single_lane`).
      active: bool[n] frontier over the *major* vertex.
      y_init: optional initial output (n rows); defaults to the semiring
        identity.

    Returns:
      (y[n, ...], IOStats) — only chunks intersecting the frontier are
      fetched; everything else is counted as skipped, exactly like
      FlashGraph eliding SSD page reads for inactive vertex ranges.
    """
    n = store.n
    x, y_init, restore = flatten_single_lane(x, y_init)
    xp = pad_state(x, sr)
    prefix = _active_prefix(active)
    y0 = _pad_y_init(sr, xp, y_init, n)
    gather_on_major = (store.sorted_by == "src") != reverse
    has_w = store.w is not None
    rec_bytes = _store_record_bytes(store.w)
    fetch = _make_fetch(sr, xp, active, n, gather_on_major, has_w)

    def body(carry, chunk):
        y, st = carry
        major, minor, w, lo, hi = chunk
        n_act = prefix[hi + 1] - prefix[lo]
        is_active = n_act > 0

        def do_fetch(args):
            y, st = args
            y, msgs = fetch(y, major, minor, w)
            st = IOStats(
                requests=st.requests + n_act,
                records=st.records + store.chunk_size,
                chunks_skipped=st.chunks_skipped,
                messages=st.messages + msgs,
                supersteps=st.supersteps,
                bytes_moved=st.bytes_moved + store.chunk_size * rec_bytes,
                x_fetches=st.x_fetches,
                host_bytes=st.host_bytes,
                retries=st.retries,
            )
            return y, st

        def do_skip(args):
            y, st = args
            return y, st._replace(chunks_skipped=st.chunks_skipped + 1)

        y, st = jax.lax.cond(is_active, do_fetch, do_skip, (y, st))
        return (y, st), None

    w_arr = store.w if has_w else jnp.zeros_like(store.major, dtype=jnp.float32)
    with jax.named_scope("graphyti.chunk_scan"):
        (y, st), _ = jax.lax.scan(
            body, (y0, IOStats.zero()),
            (store.major, store.minor, w_arr, store.lo, store.hi),
        )
    return restore(y[:n]), st


def compact_spmv(
    store: EdgeChunkStore,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    y_init: Optional[jnp.ndarray] = None,
    *,
    chunk_cap: int,
    reverse: bool = False,
    assume_fits: bool = False,
) -> tuple[jnp.ndarray, IOStats]:
    """Frontier-compacted SpMV: pay for *active* chunks, not all chunks.

    :func:`sem_spmv` is faithful about I/O accounting but still executes a
    sequential ``lax.scan`` over every chunk — a skipped chunk costs a loop
    step (and under batching both ``lax.cond`` branches), so skipping shows
    up in :class:`IOStats` while wall-clock stays O(total chunks).  This
    path makes skipping pay: the frontier's chunk-activity bitmap is
    prefix-sum compacted into a dense work-list of active chunk ids
    (``nonzero(size=chunk_cap)``), only those chunks' ``major``/``minor``/
    ``w`` rows are gathered (dynamically, one row per step), and the scan
    runs ``chunk_cap`` steps instead of ``num_chunks``.

    ``chunk_cap`` is a static capacity: when the live chunk count overflows
    it, a ``lax.cond`` falls back to the full :func:`sem_spmv` scan, so the
    result is always exact.  Because the compacted work-list preserves chunk
    order and applies the identical per-chunk fetch, the output is bitwise
    identical to :func:`sem_spmv` and the IOStats are equal field-for-field
    (requests / records / chunks_skipped / messages) on both branches.

    ``assume_fits=True`` elides the overflow test and the traced fallback
    branch entirely — ONLY for callers that already guarantee the live
    chunk count fits ``chunk_cap`` (the engine's three-way dispatch tests
    exactly that before routing here); a wrong guarantee silently truncates
    the work-list.
    """
    n = store.n
    C = store.num_chunks
    cap = max(1, min(int(chunk_cap), C))
    x, y_init, restore = flatten_single_lane(x, y_init)
    xp = pad_state(x, sr)
    prefix = _active_prefix(active)
    y0 = _pad_y_init(sr, xp, y_init, n)
    gather_on_major = (store.sorted_by == "src") != reverse
    has_w = store.w is not None
    fetch = _make_fetch(sr, xp, active, n, gather_on_major, has_w)

    per_chunk_act = prefix[store.hi + 1] - prefix[store.lo]
    act_chunk = per_chunk_act > 0
    n_act_chunks = jnp.sum(act_chunk.astype(jnp.int32))

    def compact_branch(_):
        ids = jnp.nonzero(act_chunk, size=cap, fill_value=0)[0].astype(jnp.int32)
        step_valid = jnp.arange(cap, dtype=jnp.int32) < n_act_chunks

        def body(carry, sl):
            y, msgs = carry
            cid, valid = sl
            major = store.major[cid]
            minor = store.minor[cid]
            w = store.w[cid] if has_w else None
            y, m = fetch(y, major, minor, w, valid)
            return (y, msgs + m), None

        with jax.named_scope("graphyti.chunk_scan"):
            (y, msgs), _ = jax.lax.scan(
                body, (y0, jnp.zeros((), jnp.int32)), (ids, step_valid))
        st = IOStats(
            # requests/records/skips are per-chunk facts independent of the
            # execution order — computed vectorized over the activity bitmap
            # so they equal the full scan's running totals exactly.
            requests=jnp.sum(jnp.where(act_chunk, per_chunk_act, 0)),
            records=n_act_chunks * store.chunk_size,
            chunks_skipped=C - n_act_chunks,
            messages=msgs,
            supersteps=jnp.zeros((), jnp.int32),
            bytes_moved=n_act_chunks * store.chunk_size
            * _store_record_bytes(store.w),
            x_fetches=jnp.zeros((), jnp.int32),
            host_bytes=jnp.zeros((), jnp.int32),
            retries=jnp.zeros((), jnp.int32),
        )
        return y[:n], st

    if assume_fits:
        y, st = compact_branch(None)
    else:
        def full_branch(_):
            return sem_spmv(store, x, active, sr, y_init, reverse=reverse)

        y, st = jax.lax.cond(n_act_chunks <= cap, compact_branch,
                             full_branch, None)
    return restore(y), st


def p2p_spmv(
    sg: SemGraph,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    *,
    direction: str = "out",
    vcap: int,
    ecap: int,
    y_init: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, IOStats]:
    """Point-to-point path: fetch exactly the adjacency rows of active
    vertices (one request per row, no chunk over-fetch).

    The paper's hybrid-messaging principle (coreness, §4.2): multicast
    (chunked) fetches waste bytes once the frontier is sparse; row-exact
    fetches issue more requests but move only live edges. ``vcap``/``ecap``
    bound the gather (static shapes); callers switch to this path only when
    the frontier fits, which is exactly when it is profitable.

    Active rows are the *major* side: out-rows push to dst, in-rows pull
    from src onto the active dst.
    """
    n = sg.n
    if direction == "out":
        indptr, indices, w = sg.indptr, sg.indices, sg.w
    else:
        indptr, indices, w = sg.in_indptr, sg.in_indices, sg.in_w
    if sg.m == 0:  # static: no edges, nothing to fetch
        y = sr.neutral_like(pad_state(x, sr), n) if y_init is None else y_init
        return y, IOStats.zero()
    xp = pad_state(x, sr)
    y0 = _pad_y_init(sr, xp, y_init, n)

    act_idx = jnp.nonzero(active, size=vcap, fill_value=n)[0]
    num_act = jnp.minimum(jnp.sum(active.astype(jnp.int32)), vcap)
    deg = indptr[act_idx + 1] - indptr[act_idx]
    offs = jnp.cumsum(deg)
    starts = offs - deg
    total_edges = offs[-1] if vcap > 0 else jnp.zeros((), jnp.int32)

    p = jnp.arange(ecap, dtype=jnp.int32)
    k = jnp.searchsorted(offs, p, side="right").astype(jnp.int32)
    kc = jnp.minimum(k, vcap - 1)
    valid = (p < total_edges) & (k < vcap)
    major = jnp.where(valid, act_idx[kc], n)
    e = jnp.where(valid, indptr[jnp.minimum(major, n)] + (p - starts[kc]), 0)
    minor = jnp.where(valid, indices[jnp.minimum(e, sg.m - 1)], n)
    ew = None
    if w is not None:
        ew = jnp.where(valid, w[jnp.minimum(e, sg.m - 1)], 0.0)

    gather_idx = major if direction == "out" else minor
    key = minor if direction == "out" else major
    xv = xp[gather_idx]
    contrib = sr.edge_op(xv, ew)
    if contrib.ndim > 1:
        v2 = valid.reshape((-1,) + (1,) * (contrib.ndim - 1))
    else:
        v2 = valid
    contrib = jnp.where(v2, contrib, jnp.asarray(sr.identity, contrib.dtype))
    key = jnp.where(valid, key, n)
    y = sr.scatter(y0, key, contrib)
    st = IOStats(
        requests=num_act,
        records=total_edges.astype(jnp.int32),
        chunks_skipped=jnp.zeros((), jnp.int32),
        messages=total_edges.astype(jnp.int32),
        supersteps=jnp.zeros((), jnp.int32),
        bytes_moved=(total_edges * _store_record_bytes(w)).astype(jnp.int32),
        x_fetches=jnp.zeros((), jnp.int32),
        host_bytes=jnp.zeros((), jnp.int32),
        retries=jnp.zeros((), jnp.int32),
    )
    return y[:n], st
