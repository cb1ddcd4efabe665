"""Host-side blocked-graph format + jit'd wrapper around the SpMV kernel.

``build_blocked`` converts a CSR :class:`repro.graph.csr.Graph` into the
dense-tile format the kernel streams: vertices are split into destination
blocks of ``Bd`` rows and source blocks of ``Bs`` columns; every (dst_block,
src_block) pair containing at least one edge becomes one dense ``(Bd, Bs)``
weight tile.

``tile_order`` picks the streaming schedule.  The default ``'dest'`` sorts
tiles by destination block so each block is one contiguous *run* and the
kernel's VMEM accumulator flushes once per block.  ``'morton'`` /
``'hilbert'`` order tiles along a space-filling curve over the
(dst_block, src_block) grid instead (see :mod:`.order`): consecutive tiles
stay adjacent in both coordinates, so the single resident x window is
reused across steps instead of re-fetched once per destination row — the
locality lever for skewed graphs.  Under a curve order one destination
block occupies several non-contiguous runs, so ``first``/``last`` are
per-RUN flags and a run whose block was already flushed carries
``accum=1``: its flush combines into ``y`` rather than overwriting
(equivalent to every flush accumulating into a zero-initialized ``y`` —
the first run's overwrite supplies the zero-init without an HBM-cleared
output buffer).

This mirrors FlashGraph's edge-page layout: a tile is a "page", the per-tile
``sbid`` is the page's vertex range, and the frontier-activity vector decides
which pages are fetched.  ``blocked_spmv`` counts fetched/skipped tiles so
the kernel path reports the same I/O metrics as the jnp engine.

Frontier granularity: activity can key on **source** blocks (push-style —
a tile is fetched iff its column range holds an active vertex) or on
**destination** blocks (pull-style — a tile is fetched iff its row range
holds an active vertex); see ``blocked_spmv(active_on=...)``.  ``reverse``
tiling transposes the operator (rows = sources, columns = destinations) for
message flows that run against the edge direction, e.g. betweenness
backward propagation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...graph.csr import Graph
from .kernel import spmv_pallas, spmv_pallas_compact
from .order import TILE_ORDERS, tile_curve_key

__all__ = [
    "BlockedGraph",
    "TILE_ORDERS",
    "build_blocked",
    "build_blocked_arrays",
    "blocked_spmv",
    "compact_grid_size",
    "compact_tile_order",
    "default_interpret",
    "tile_activity",
    "tile_byte_size",
    "x_fetch_count",
]


def default_interpret() -> bool:
    """Pallas interpret mode everywhere except a real TPU backend."""
    return jax.default_backend() != "tpu"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BlockedGraph:
    """Dense-tile blocked view of a graph (edges as (Bd, Bs) MXU tiles)."""

    tiles: jnp.ndarray  # [T, Bd, Bs] f32 edge weights (0 or +inf = absent)
    dbid: jnp.ndarray  # [T] int32 destination block ids (schedule order)
    sbid: jnp.ndarray  # [T] int32 source block ids
    first: jnp.ndarray  # [T] int32 — tile starts a run of its dst block
    last: jnp.ndarray  # [T] int32 — tile ends a run of its dst block
    accum: jnp.ndarray  # [T] int32 — run's flush combines into y (block
    #   already flushed by an earlier run; always 0 under 'dest' order)
    nnz: jnp.ndarray  # [T] int32 — edge records baked into each tile
    n: int = dataclasses.field(metadata=dict(static=True))
    bd: int = dataclasses.field(metadata=dict(static=True))
    bs: int = dataclasses.field(metadata=dict(static=True))
    semiring: str = dataclasses.field(metadata=dict(static=True))
    tile_order: str = dataclasses.field(metadata=dict(static=True),
                                        default="dest")

    @property
    def num_tiles(self) -> int:
        return int(self.tiles.shape[0])

    @property
    def n_dst_blocks(self) -> int:
        return -(-self.n // self.bd)

    @property
    def n_src_blocks(self) -> int:
        return -(-self.n // self.bs)


def _run_flags(dbid: np.ndarray, n_dst_blocks: int):
    """(first, last, accum) int32 run flags over a tile schedule.

    A *run* is a maximal stretch of consecutive tiles sharing a destination
    block.  ``first``/``last`` mark run boundaries; ``accum`` marks runs
    whose block was already flushed by an earlier run, so their flush must
    combine into ``y`` instead of overwriting.  Under sorted ``'dest'``
    order every block is exactly one run and ``accum`` is all zero — the
    historical kernel contract falls out as the special case.
    """
    T = len(dbid)
    first = np.ones(T, np.int32)
    first[1:] = (dbid[1:] != dbid[:-1]).astype(np.int32)
    last = np.ones(T, np.int32)
    last[:-1] = (dbid[1:] != dbid[:-1]).astype(np.int32)
    starts = np.flatnonzero(first)
    run_db = dbid[starts].astype(np.int64)
    n_runs = len(starts)
    first_run = np.full(max(1, n_dst_blocks), n_runs, np.int64)
    np.minimum.at(first_run, run_db, np.arange(n_runs))
    accum_run = (np.arange(n_runs) > first_run[run_db]).astype(np.int32)
    accum = accum_run[np.cumsum(first) - 1]
    return first, last, accum


def build_blocked_arrays(
    g: Graph,
    *,
    bd: int = 128,
    bs: int = 128,
    direction: str = "out",
    semiring: str = "plus_times",
    reverse: bool = False,
    tile_order: str = "dest",
) -> dict:
    """Numpy core of :func:`build_blocked`: the tile arrays as plain host
    arrays.  The ``residency='host'`` path pins exactly these in host RAM
    (:class:`repro.core.residency.HostBlockedStore`) and ships live tiles
    on demand; :func:`build_blocked` wraps them as device arrays — one
    tiler, so both residencies stream byte-identical tiles in the same
    schedule."""
    if tile_order not in TILE_ORDERS:
        raise ValueError(
            f"unknown tile_order {tile_order!r}; expected one of {TILE_ORDERS}"
        )
    if direction == "out":
        indptr, indices, w = g.indptr, g.indices, g.weights
    else:
        assert g.in_indptr is not None
        indptr, indices, w = g.in_indptr, g.in_indices, g.in_weights
    n = g.n
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    dst = indices.astype(np.int64)
    if direction == "in":  # in-CSR rows are destinations
        src, dst = dst, src
    if w is None or semiring == "bool":
        # Unweighted edges carry the semiring's edge_op identity: 1 under
        # plus_times (y += 1 * x), 0 under min_plus (y = min(0 + x)) —
        # matching sem_spmv/coo semantics where a missing weight is a no-op.
        # 'bool' tiles ignore weights entirely (occupancy = 1 per edge).
        fill = 0.0 if semiring == "min_plus" else 1.0
        wv = np.full(len(src), fill, np.float32)
    else:
        wv = w.astype(np.float32)

    # Tile coordinates: rows are the scatter side, columns the gather side.
    row, col = (src, dst) if reverse else (dst, src)
    db, sb = row // bd, col // bs
    key = db * (-(-n // bs)) + sb
    order = np.argsort(key, kind="stable")
    db, sb, row, col, wv = db[order], sb[order], row[order], col[order], wv[order]
    uniq, start = np.unique(key[order], return_index=True)

    T = max(1, len(uniq))
    absent = np.inf if semiring == "min_plus" else 0.0
    tiles = np.full((T, bd, bs), absent, np.float32)
    dbid = np.zeros(T, np.int32)
    sbid = np.zeros(T, np.int32)
    nnz = np.zeros(T, np.int32)
    if len(uniq):
        ends = np.append(start[1:], len(db))
        for t, (s0, s1) in enumerate(zip(start, ends)):
            dbid[t] = db[s0]
            sbid[t] = sb[s0]
            nnz[t] = s1 - s0
            rows = (row[s0:s1] - db[s0] * bd).astype(np.int64)
            cols = (col[s0:s1] - sb[s0] * bs).astype(np.int64)
            if semiring == "min_plus":
                np.minimum.at(tiles[t], (rows, cols), wv[s0:s1])
            elif semiring == "bool":
                tiles[t][rows, cols] = 1.0  # occupancy, multi-edges idempotent
            else:
                np.add.at(tiles[t], (rows, cols), wv[s0:s1])
    n_dst_blocks = -(-n // bd)
    if tile_order != "dest" and T > 1:
        # Re-schedule the SAME tiles along the curve: only the stream order
        # (and hence the run structure) changes; the tile contents and the
        # per-tile activity semantics are untouched.
        ck = tile_curve_key(dbid, sbid, n_dst_blocks, -(-n // bs), tile_order)
        p = np.argsort(ck, kind="stable")
        tiles, dbid, sbid, nnz = tiles[p], dbid[p], sbid[p], nnz[p]
    first, last, accum = _run_flags(dbid, n_dst_blocks)
    return dict(
        tiles=tiles,
        dbid=dbid,
        sbid=sbid,
        first=first,
        last=last,
        accum=accum,
        nnz=nnz,
        n=n,
        bd=bd,
        bs=bs,
        semiring=semiring,
        tile_order=tile_order,
    )


def build_blocked(
    g: Graph,
    *,
    bd: int = 128,
    bs: int = 128,
    direction: str = "out",
    semiring: str = "plus_times",
    reverse: bool = False,
    tile_order: str = "dest",
) -> BlockedGraph:
    """Tile ``g``'s edges into dense (bd, bs) blocks (host side, numpy).

    ``direction='out'`` builds y[dst] (+)= x[src] tiles (push); ``'in'``
    sources the same operator from the in-CSR.  ``reverse=True`` transposes
    the operator — y[src] (+)= x[dst] — which is the tile view betweenness
    backward propagation streams (messages against the edge direction).
    Absent edges hold the semiring annihilator (0 for plus_times/bool, +inf
    for min_plus).

    ``semiring='bool'`` builds *occupancy* tiles: every edge slot holds 1
    regardless of weights, so boolean (or_and) frontiers are exact even on
    weighted graphs with zero or negative weights.  They run on the
    plus_times kernel.

    ``tile_order`` ('dest' | 'morton' | 'hilbert') picks the streaming
    schedule — the SAME tiles in a locality-aware order (see the module
    docstring and :mod:`.order`).  The tile set, activity semantics, and
    I/O accounting other than the x-fetch counter are order-invariant.
    """
    a = build_blocked_arrays(g, bd=bd, bs=bs, direction=direction,
                             semiring=semiring, reverse=reverse,
                             tile_order=tile_order)
    return BlockedGraph(
        tiles=jnp.asarray(a["tiles"]),
        dbid=jnp.asarray(a["dbid"]),
        sbid=jnp.asarray(a["sbid"]),
        first=jnp.asarray(a["first"]),
        last=jnp.asarray(a["last"]),
        accum=jnp.asarray(a["accum"]),
        nnz=jnp.asarray(a["nnz"]),
        n=a["n"],
        bd=a["bd"],
        bs=a["bs"],
        semiring=a["semiring"],
        tile_order=a["tile_order"],
    )


def compact_tile_order(bg: BlockedGraph, act_tile: jnp.ndarray):
    """Compact live tiles to the grid front; returns the permuted schedule.

    ``act_tile`` (int/bool[T]) is stably compacted — ``nonzero`` yields
    ascending tile ids, so the schedule order (hence per-run float
    rounding) is unchanged.  Tail slots (``pos >= nact``) repeat the LAST
    live tile's coordinates: the tile, its x block, and its output block
    are all still resident from the previous step, so the tail issues no
    DMA.  ``first``/``last`` are recomputed over the permuted order and
    forced to 0 on the tail so the accumulator is neither re-zeroed nor
    re-flushed.

    Run contiguity under curve orders: boundaries key on the ORIGINAL run
    id (``cumsum(bg.first)``), not on dst-block adjacency — when every
    tile between two runs of one block goes inactive, the runs become
    adjacent in the compacted schedule but are NOT merged, so each run
    accumulates exactly the tiles (in the order) the full grid gave it and
    the result stays bitwise identical.  ``accum`` is recomputed over the
    LIVE runs: the first surviving run of each block flushes by overwrite
    (supplying the zero-init), later ones combine.

    Returns ``(perm, dbid, sbid, first, last, accum, nact)`` — all
    int32[T] plus the scalar live count.
    """
    T = bg.num_tiles
    act = act_tile.astype(jnp.int32)
    nact = jnp.sum(act)
    ids = jnp.nonzero(act > 0, size=T, fill_value=0)[0].astype(jnp.int32)
    last_live = ids[jnp.maximum(nact - 1, 0)]
    pos = jnp.arange(T, dtype=jnp.int32)
    valid = pos < nact
    perm = jnp.where(valid, ids, last_live)
    dbid = bg.dbid[perm]
    sbid = bg.sbid[perm]
    run = (jnp.cumsum(bg.first) - 1)[perm]  # original run id per step
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), run[:-1]])
    nxt = jnp.concatenate([run[1:], jnp.full((1,), -1, jnp.int32)])
    first = (valid & (run != prev)).astype(jnp.int32)
    # the last live step must flush even though the tail repeats its run.
    last = (valid & ((run != nxt) | (pos == nact - 1))).astype(jnp.int32)
    # accum over live runs: a run combines iff an earlier live position
    # already flushed its dst block (first live position < this run's
    # start, found via a cummax over run-start positions).
    first_pos = jnp.full(bg.n_dst_blocks, T, jnp.int32).at[dbid].min(
        jnp.where(valid, pos, T)
    )
    run_start = jax.lax.cummax(jnp.where(first == 1, pos, -1))
    accum = (valid & (first_pos[dbid] < run_start)).astype(jnp.int32)
    return perm, dbid, sbid, first, last, accum, nact


def compact_grid_size(num_tiles: int, num_active: int) -> int:
    """Smallest power-of-two grid covering ``num_active``, capped at T.

    Only log2(T) distinct sizes exist, so pre-jitting one kernel per bucket
    is cheap while a tiny frontier gets a tiny grid.
    """
    g = 1
    while g < max(1, num_active):
        g *= 2
    return min(g, max(1, num_tiles))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _compact_spmv_jit(bg: BlockedGraph, x_blocks, perm, dbid, sbid, first,
                      last, accum, nact, interpret: bool):
    return spmv_pallas_compact(
        bg.tiles,
        perm,
        dbid,
        sbid,
        first,
        last,
        accum,
        nact,
        x_blocks,
        bg.n_dst_blocks,
        semiring=bg.semiring,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _blocked_spmv_jit(bg: BlockedGraph, x_blocks, act_tile, interpret: bool):
    return spmv_pallas(
        bg.tiles,
        bg.dbid,
        bg.sbid,
        bg.first,
        bg.last,
        bg.accum,
        act_tile,
        x_blocks,
        bg.n_dst_blocks,
        semiring=bg.semiring,
        interpret=interpret,
    )


def x_fetch_count(sbid: jnp.ndarray, act_tile: jnp.ndarray) -> jnp.ndarray:
    """int32 scalar: x-block DMAs the LIVE schedule issues.

    The kernel holds a single resident x window, so a DMA fires exactly
    when consecutive live steps name different source blocks (plus one for
    the first live step).  This is the fetch count of the compacted grid,
    which streams the live subsequence verbatim; the full grid's
    inactive-step index-map redirects to block 0 can add fetches on top,
    but those are an artifact of the redirect trick, not of the schedule —
    the counter charges the schedule so the full and compacted executions
    of one (order, frontier) pair report the same number, and only the
    tile ORDER moves it.  This is the quantity ``tile_order`` exists to
    minimize (``tests/test_tile_order.py`` counts it).
    """
    T = int(sbid.shape[0])
    act = act_tile.astype(bool)
    pos = jnp.arange(T, dtype=jnp.int32)
    # index of the previous live step (exclusive), -1 when none yet.
    prev_live = jax.lax.cummax(jnp.where(act, pos, -1))
    prev_live = jnp.concatenate(
        [jnp.full((1,), -1, jnp.int32), prev_live[:-1]]
    )
    prev_sb = sbid[jnp.maximum(prev_live, 0)]
    fetch = act & ((prev_live < 0) | (sbid != prev_sb))
    return jnp.sum(fetch.astype(jnp.int32))


def tile_activity(
    bg: BlockedGraph, active: jnp.ndarray, active_on: str = "src"
) -> jnp.ndarray:
    """int32[T] 0/1 — which tiles a frontier would fetch.

    ``active_on='src'``: a tile is live iff its source block (columns)
    intersects the frontier — push/multicast skipping (paper P1).
    ``active_on='dst'``: a tile is live iff its destination block (rows)
    intersects the frontier — pull skipping (only active destinations
    fetch their in-edge pages).
    """
    n = bg.n
    if active_on == "src":
        pad = bg.n_src_blocks * bg.bs
        ap = jnp.zeros(pad, bool).at[:n].set(active)
        act_blk = ap.reshape(bg.n_src_blocks, bg.bs).any(axis=1)
        return act_blk[bg.sbid].astype(jnp.int32)
    if active_on == "dst":
        pad = bg.n_dst_blocks * bg.bd
        ap = jnp.zeros(pad, bool).at[:n].set(active)
        act_blk = ap.reshape(bg.n_dst_blocks, bg.bd).any(axis=1)
        return act_blk[bg.dbid].astype(jnp.int32)
    raise ValueError(f"active_on must be 'src' or 'dst', got {active_on!r}")


def tile_byte_size(bg: BlockedGraph) -> int:
    """Bytes one tile actually ships: dense f32 slots for the numeric
    semirings, a 1-bit-per-slot bitmap for 'bool' occupancy tiles (which
    carry no magnitudes, so 4 bytes/slot would overcharge them 32x)."""
    if bg.semiring == "bool":
        return (bg.bd * bg.bs) // 8
    return bg.bd * bg.bs * 4


def blocked_spmv(
    bg: BlockedGraph,
    x: jnp.ndarray,
    active: Optional[jnp.ndarray] = None,
    *,
    active_on: str = "src",
    interpret: Optional[bool] = None,
    compact: bool = False,
    grid_bucket: Optional[int] = None,
    assume_fits: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """y = A (.) x over the blocked tiles, with frontier tile skipping.

    Args:
      x: [n] or [n, K] vertex state (K = multi-source lanes).
      interpret: Pallas interpret mode; ``None`` resolves through
        :func:`default_interpret` (compiled on a TPU, interpreted elsewhere).
      active: optional bool[n] frontier; tiles disjoint from it are skipped
        (fetch + compute).  With ``active_on='src'`` the frontier lives on
        source vertices (columns; push multicast), with ``'dst'`` on
        destination vertices (rows; pull gather).  Skipping is *block*
        granular: an active block applies whole tiles, so callers needing
        row/column-exact semantics mask ``x`` (or the output rows)
        themselves — :func:`repro.core.engine.spmv` does exactly that.
      compact: route through the frontier-compacted grid
        (:func:`repro.kernels.spmv.kernel.spmv_pallas_compact`): live tiles
        are permuted to the grid front and the tail no-ops on resident
        blocks, so a sparse frontier costs ~``num_active`` real steps.
        When ``active`` is concrete (outside jit) the grid itself shrinks
        to the next power of two over the live count — size-bucketed so at
        most log2(T) kernel variants ever compile.  Results are bitwise
        identical to the full grid (same tiles, same order).
      grid_bucket: static work-list capacity (in tiles) for the compacted
        grid *under jit*, where the live count is traced and the grid
        would otherwise stay at full T capacity.  The grid shrinks to the
        pow2 bucket over this cap; if the live count overflows it, a
        ``lax.cond`` falls back to the full-capacity grid, so the result
        is always exact.  This is how the engine's
        :class:`~repro.core.engine.ExecutionPolicy` sizes the Pallas grid
        from its ``chunk_cap``.
      assume_fits: elide that overflow guard — ONLY for callers that
        already proved the live tile count fits ``grid_bucket`` (the
        engine's dispatch tests exactly that before routing here).

    Returns:
      (y [n] or [n, K] f32, stats) — stats counts fetched/skipped tiles,
      tile bytes moved (layout-aware: f32 slots, or 1/32 of that for
      'bool' bitmap tiles), the edge records resident in fetched tiles
      (``messages`` — block-granular, so >= the row-exact count), and the
      x-block DMA count of the live schedule (``x_fetches`` — the ONE
      counter ``bg.tile_order`` moves; see :func:`x_fetch_count`), the
      kernel-path analogue of ``core.sem.IOStats``.  Identical across the
      full and compacted grids.
    """
    if interpret is None:
        interpret = default_interpret()
    if not interpret and bg.tile_order != "dest":
        # The accumulate-on-flush read of a revisited output block is exact
        # in interpret mode (every step operates on the real buffer) but is
        # NOT yet validated against Mosaic's output-window pipelining on
        # physical TPUs — refuse rather than risk silently stale reads.
        raise ValueError(
            f"tile_order={bg.tile_order!r} is only supported in interpret "
            "mode for now (compiled TPU output-window revisits are "
            "unvalidated); use tile_order='dest' or interpret=True"
        )
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    k = x.shape[1]
    n, bd, bs = bg.n, bg.bd, bg.bs
    pad_n = bg.n_src_blocks * bs
    ident = jnp.inf if bg.semiring == "min_plus" else 0.0
    xp = jnp.full((pad_n, k), ident, x.dtype).at[:n].set(x)
    x_blocks = xp.reshape(bg.n_src_blocks, bs, k).astype(jnp.float32)

    if active is None:
        act_tile = jnp.ones(bg.num_tiles, jnp.int32)
    else:
        act_tile = tile_activity(bg, active, active_on)

    ident_out = jnp.inf if bg.semiring == "min_plus" else 0.0
    if compact:
        (perm, dbid_p, sbid_p, first_p, last_p, accum_p,
         nact) = compact_tile_order(bg, act_tile)
        T = bg.num_tiles

        def _run_grid(G):
            return _compact_spmv_jit(
                bg, x_blocks, perm[:G], dbid_p[:G], sbid_p[:G], first_p[:G],
                last_p[:G], accum_p[:G], jnp.reshape(nact, (1,)), interpret,
            )

        if not isinstance(nact, jax.core.Tracer):
            # concrete frontier: exact pow2 bucket over the live count.
            y_blocks = _run_grid(compact_grid_size(T, int(nact)))
        elif grid_bucket is None:
            # traced frontier, no cap: full-capacity grid, tail no-ops.
            y_blocks = _run_grid(T)
        else:
            G = compact_grid_size(T, min(int(grid_bucket), T))
            if assume_fits or G >= T:
                y_blocks = _run_grid(G)
            else:
                # the bucket is a hint, not a guarantee: overflow falls
                # back to the full-capacity grid (bitwise-identical).
                y_blocks = jax.lax.cond(
                    nact <= G,
                    lambda _: _run_grid(G),
                    lambda _: _run_grid(T),
                    None,
                )
        # Blocks with no LIVE tile are never flushed (the compacted grid
        # never visits them) — fill with the accumulate identity, exactly
        # what the full grid's zeroed-then-flushed accumulator yields.
        flushed = (
            jnp.zeros(bg.n_dst_blocks, jnp.int32).at[bg.dbid].max(act_tile) > 0
        )
        y_blocks = jnp.where(flushed[:, None, None], y_blocks, ident_out)
    else:
        y_blocks = _blocked_spmv_jit(bg, x_blocks, act_tile, interpret)
        # The grid walks only existing tiles, so a destination block owning
        # NO tiles is never flushed and its output rows stay uninitialized
        # (NaN in interpret mode, garbage on TPU).  Fill them with the
        # accumulate identity, matching what an all-absent tile would have
        # flushed.
        has_db = jnp.zeros(bg.n_dst_blocks, bool).at[bg.dbid].set(True)
        y_blocks = jnp.where(has_db[:, None, None], y_blocks, ident_out)
    y = y_blocks.reshape(bg.n_dst_blocks * bd, k)[:n]
    if squeeze:
        y = y[:, 0]
    fetched = jnp.sum(act_tile)
    stats = {
        "tiles_fetched": fetched,
        "tiles_skipped": bg.num_tiles - fetched,
        "tile_bytes": fetched * tile_byte_size(bg),
        "messages": jnp.sum(bg.nnz * act_tile),
        # order-sensitive: everything above is a per-tile sum (invariant
        # under the schedule permutation); this one is what tile_order buys.
        "x_fetches": x_fetch_count(bg.sbid, act_tile),
    }
    return y, stats
