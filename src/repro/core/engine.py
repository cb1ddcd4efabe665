"""The ExecutionPolicy dispatch stack, one-superstep traverse, and baselines.

The engine mirrors FlashGraph's execution model:

  * :class:`ExecutionPolicy` + :func:`traverse` — ONE object owning every
    execution decision the paper assigns to the framework rather than the
    application (§4.2, "the engine owns I/O minimization"): multicast
    backend, work-list capacities, push/pull direction, and all switch
    thresholds.  Algorithms pass a policy; the engine picks the cheapest
    execution per superstep.
  * :func:`flat_spmv` — the *in-memory* baseline: one unchunked segment
    reduction over all m edges, no skipping, no counting.  This is what the
    "SEM achieves 80% of in-memory performance" claim is measured against.

Every runtime guard in this module raises a typed error —
:class:`PolicyError` for a bad knob, :class:`ResidencyError` for a
missing view — and each has a *static* counterpart in
:mod:`repro.analysis` (jaxpr rules R1–R6) and ``tools/semlint.py``
(AST rules S1–S3): what the dispatch would reject mid-run,
``Graph.run(analyze=True)`` rejects before any edge byte moves.

Algorithms do not normally call this module directly: they are
:class:`~repro.core.program.VertexProgram` instances, and
:func:`~repro.core.program.run_program` — the library's single BSP driver —
calls :func:`traverse` once per superstep on their behalf.  ``run_program``
is also the plug-in point for everything on the ROADMAP (Hilbert tile
order, multi-device sharding, refined direction gates): a new policy field
picked up by the dispatch below reaches every algorithm, built-in or
user-written, with no per-algorithm work.

Four-way dispatch
-----------------
:func:`traverse` composes two orthogonal switches, both under ``lax.cond``
so only one path does work per superstep:

**Direction (push vs pull, Beamer-style).**  A frontier's logical action is
"multicast my value along my out-edges".  Two executions exist:

  * **push** (``direction='out'``): stream the *frontier's* out-edge
    chunks/tiles, scatter onto destinations.  Cost tracks the frontier's
    edge mass ``m_f``.
  * **pull** (``direction='in'``): stream the *candidate* (unexplored)
    vertices' in-edge chunks/tiles, gather from frontier sources.  Cost
    tracks the unexplored mass ``m_u`` — far smaller than ``m_f`` in the
    middle supersteps of a BFS on a low-diameter graph, where the frontier
    covers most edges but almost everything is already explored.

  With ``direction='auto'`` the engine applies Beamer's α/β heuristic per
  superstep: pull when ``m_f · α > m_u`` (the frontier's mass overwhelms
  what is left to discover) AND ``n_f · β > n`` (the frontier is not so
  narrow that streaming candidate in-chunks over-fetches); push otherwise.
  The decision is a device-side ``lax.cond`` — no host round-trip — and
  accounting stays execution-invariant: ``messages`` always reports the
  frontier's logical out-edge mass, whichever direction executed it
  (compaction and direction change wall-clock and bytes, never the logical
  message count).

**Density (multicast / compact / p2p).**  Within the chosen direction, with
C fetch units (chunks or tiles), A live units, e live edge mass, S the unit
size:

  * dense multicast  — O(C·S) work, best throughput per edge when most
    units are live (A ≈ C): no compaction overhead, contiguous streaming.
  * compact          — O(C) activity test + O(cap·S) work over a
    prefix-sum-compacted work-list of live units.  Wins in the mid-density
    band where A << C but e is still too large for p2p's static gather.
    For the scan backend this is :func:`repro.core.sem.compact_spmv`; for
    the blocked backend it is the permuted Pallas grid sized to the
    policy's pow2 bucket.  ``adaptive_cap=True`` re-buckets the work-list
    per superstep (``lax.switch`` over the pow2 sizes) from the live-unit
    count, so a draining BFS runs each superstep on the smallest compiled
    bucket that fits it.
  * point-to-point   — O(ecap) gathered edge slots, row-exact bytes.  Wins
    on the sparse tail (e <= switch_fraction·m and the static ``vcap`` /
    ``ecap`` capacities fit), where even one live unit per live vertex
    over-fetches.

**Residency (device vs host, the SEM axis).**  Orthogonal to both switches
above: ``ExecutionPolicy.residency`` decides where the O(m) edge store
*lives*.  ``'device'`` (default) keeps chunk/tile arrays in device memory —
streaming is simulated, fetch/skip decisions are counted but every byte is
already resident.  ``'host'`` pins the edge store in host RAM
(:mod:`repro.core.residency`) and ships only the live work-list per
superstep, double-buffered (`jax.device_put` of batch k+1 dispatched while
batch k computes), so peak device bytes are O(n) vertex state plus
O(stream_buffer) staging — true semi-external memory.  The cost model
gains a host-link term: a host superstep pays ``live_bytes / B_link``
transfer time overlapped against compute, so it runs at compute-bound
speed when ``B_link * t_compute >= live_bytes`` and degrades gracefully to
link-bound streaming otherwise (the paper's "80% of in-memory" regime is
exactly the overlapped case).  ``IOStats.host_bytes`` measures that
traffic; every other order-invariant field — and the values — are
bitwise-identical across residencies, which is the refactor's safety net.

**Batched queries (the Q axis).**  ``active`` (and ``unexplored``) may be
(n, Q) matrices — Q concurrent traversals sharing one edge stream.  The
engine fetches for the *union* of the per-query frontiers and identity-
masks each lane's x by its own frontier, so every query combines exactly
the contributions its solo run would (the union adds only identity terms
to other lanes).  The cost model gains a Q term: one superstep's fetch
cost is ``cost(union frontier)`` — between ``max_q cost(frontier_q)`` (at
full overlap) and ``sum_q cost(frontier_q)`` (disjoint frontiers) — while
Q sequential sweeps always pay the sum.  Per-query amortized I/O
(``host_bytes / Q`` under residency='host') therefore drops toward 1/Q as
frontiers overlap, which is the serving-path headline
(``tests/test_multisource.py`` pins the drop).  Every dispatch decision
(Beamer direction, density three-way, pow2 cap buckets) keys on the union
masses, so a batched superstep executes exactly like a single-query sweep
of the union frontier; ``messages`` alone stays per-lane-exact (the sum
over queries of each query's logical edge mass).

Backends
--------
The multicast/compact step has four interchangeable executions, selected by
``ExecutionPolicy.backend`` (or ``backend=`` on :func:`spmv`):

  * ``'scan'`` — :func:`repro.core.sem.sem_spmv`: a ``lax.scan`` over
    fixed-size edge chunks with per-chunk activity tests.  Runs anywhere,
    needs only the chunk stores, and is row-exact in its I/O accounting.
  * ``'compact'`` — :func:`repro.core.sem.compact_spmv`: the frontier-
    compacted scan (work-list of live chunk ids, cap-length loop).
  * ``'blocked'`` — :func:`repro.kernels.spmv.blocked_spmv`: the Pallas TPU
    kernel streaming dense (Bd, Bs) edge tiles through the MXU, double-
    buffering each tile's HBM->VMEM DMA behind the previous tile's matmul —
    the TPU-native analogue of SAFS async reads overlapping compute.
    Requires ``device_graph(..., blocked=True)``.
  * ``'blocked_compact'`` — the same kernel on the frontier-compacted
    (permuted, size-bucketed) grid.

**Tile order (locality-aware streaming, blocked backends only).**  The
blocked kernel holds a single resident x window, so its x-block DMA count
is a property of the tile *schedule*: under the default ``tile_order=
'dest'`` (tiles sorted by destination block) the source block changes at
nearly every step, and on a skewed graph the hub columns' x blocks are
re-fetched once per destination row they touch.  ``tile_order='hilbert'``
(or the cheaper ``'morton'``) streams the SAME tiles along a space-filling
curve over the (dst_block, src_block) grid: consecutive tiles stay
adjacent in both coordinates, so roughly half the steps reuse the resident
x block — cache-aware scheduling of edge blocks in the GraphMP sense, not
just skipping them.  The order changes ONLY the schedule: values, tile
fetches, records, and bytes are order-invariant (the per-run flush
accumulates, so a destination block split across several curve runs sums
to the same result); the one counter that moves is ``IOStats.x_fetches``,
which ``tests/test_tile_order.py`` counts.  The blocked view must be
built with the matching order (``device_graph(..., tile_order=...)``);
``repro.Graph`` sessions key their tile cache by ``(encoding,
tile_order)`` and handle this automatically.

All backends serve both directions: push keys activity on source
blocks/chunks and masks inactive senders; pull keys activity on
destination blocks/chunks and masks inactive receiver rows — row-exact
either way, identical to the scan path.

IOStats are reported in the same units by all multicast backends:
``requests`` counts active major vertices whose block/chunk was fetched,
``records`` the edge-record-equivalent of data actually moved,
``bytes_moved`` the layout-aware real bytes (weighted rows 12 B, bool
occupancy tiles 1 bit/slot), ``chunks_skipped`` the elided fetch units, and
``messages`` the row-exact logical message count (invariant across
backends, compaction, AND direction).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .sem import (
    EDGE_RECORD_BYTES,
    IOStats,
    SemGraph,
    _pad_y_init,
    bucket_index,
    chunk_activity,
    compact_spmv,
    frontier_edge_mass,
    p2p_spmv,
    pad_state,
    pow2_buckets,
    sem_spmv,
)
from .semiring import Semiring

__all__ = [
    "ExecutionPolicy",
    "PolicyError",
    "ResidencyError",
    "as_policy",
    "batched_union_frontier",
    "beamer_use_pull",
    "hybrid_spmv",
    "flat_spmv",
    "spmv",
    "traverse",
    "blocked_backend_spmv",
]

State = Any


# --------------------------------------------------------------------------
# Error taxonomy: every guard in the dispatch raises a *named* subclass so
# runtime errors and `repro.analysis` diagnostics share one vocabulary.
# Both subclass ValueError, so pre-existing `except ValueError` /
# `pytest.raises(ValueError)` call sites keep working unchanged.
#
# Static-analysis cross-reference (see README "Static analysis" and
# ``repro.analysis.rules``): PolicyError guards are the runtime face of
# semlint's policy checks (rule R3 flags the non-hashable-policy variant
# before the cache silently degrades); ResidencyError guards are the
# runtime face of rule R1 (O(m) residency contract) — `analyze()` reports
# both pre-flight, before any edge data moves.
# --------------------------------------------------------------------------
class PolicyError(ValueError):
    """An :class:`ExecutionPolicy` field value (or combination) is invalid.

    Raised by policy validation and backend dispatch when the *policy
    itself* is wrong — unknown backend/direction/tile_order names, bad
    stream parameters.  Static counterpart: ``tools/semlint.py`` rule S2
    (frozen-policy mutation) and ``repro.analysis`` rule R3 (policy
    hashability, which the trace caches depend on).
    """


class ResidencyError(ValueError):
    """The policy asks for a view/residency the graph does not have.

    Raised when dispatch meets a graph missing the required edge view
    (blocked tiles, in-CSR, tile order, semiring encoding) or when policy
    residency contradicts where the edge store actually lives (host policy
    on a device store and vice versa).  Static counterpart:
    ``repro.analysis`` rules R1 (device-materialized O(m) avals under
    ``residency='host'``) and R2 (host-sync inside the traced BSP body).
    """


# --------------------------------------------------------------------------
# ExecutionPolicy: the one object algorithms hand the engine
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """Every dispatch knob in one place (replaces the kwarg sprawl).

    Attributes:
      backend: multicast execution — 'scan' | 'compact' | 'blocked' |
        'blocked_compact' (see the module docstring).
      direction: 'out' (push), 'in' (pull), or 'auto' (Beamer-style
        per-superstep switching — only meaningful for frontier-expansion
        traversals, where :func:`traverse` receives an ``unexplored`` set;
        otherwise 'auto' degrades to push).
      chunk_cap: static work-list capacity for the compact mid-band, in
        the backend's fetch units (chunks for 'scan'/'compact', tiles for
        the blocked backends).  ``None`` disables the mid-band.
      adaptive_cap: re-bucket the compact work-list per superstep to the
        smallest pow2 size fitting the live-unit count (``lax.switch``
        over the ~log2(cap) compiled buckets — no host round-trip).
      vcap / ecap: static vertex/edge capacities of the point-to-point
        gather; ``None`` resolves to n and to the gate's edge bound
        ``int(switch_fraction * m)`` (:func:`p2p_caps`): always exact,
        since the arm never opens on more edges than that.
      switch_fraction: p2p engages when the frontier's edge mass is at
        most this fraction of m (and the caps fit).  ``None`` disables
        p2p entirely.
      compact_fraction: the compact mid-band engages only while the live
        unit count is at most this fraction of all units (past it, the
        compaction gather costs more than the steps it saves).
      alpha / beta: Beamer's direction-switch thresholds — pull when
        ``m_f * alpha > m_u`` and ``n_f * beta > n`` (defaults follow the
        Beamer paper's (14, 24) neighborhood).
      tile_order: streaming schedule of the blocked backends' tile grid —
        'dest' (destination-sorted; one accumulator run per block),
        'morton' or 'hilbert' (space-filling curve; reuses the resident
        x block across consecutive tiles, cutting x-block DMA re-fetches
        on skewed graphs).  Results and all IOStats except ``x_fetches``
        are order-invariant; the graph's blocked view must be built with
        the same order (``repro.Graph`` sessions do this automatically).
        Ignored by the scan/compact backends.
      interpret: force Pallas interpret mode for the blocked backends
        (``None`` = auto: interpret everywhere but real TPUs).
      residency: where the O(m) edge store lives — 'device' (default; the
        whole chunk/tile store is device-resident, streaming is simulated)
        or 'host' (edges pinned in host RAM, live chunks/tiles shipped per
        superstep with double-buffered ``jax.device_put``; peak device
        bytes O(n) + O(stream_buffer)).  Values and all order-invariant
        IOStats fields are bitwise-identical across residencies; 'host'
        additionally measures its link traffic in ``IOStats.host_bytes``.
        Run host policies through ``repro.Graph`` (which builds the host
        view) or :func:`repro.core.residency.host_graph`.
      stream_buffer: staging batch size of the 'host' streaming executor,
        in fetch units (chunks for scan/compact, tiles for the blocked
        backends).  Two buffers of this size are in flight at the peak
        (one computing, one copying).  Ignored when residency='device'.
      stream_retries: bounded retry budget of the 'host' streaming path —
        a transient ``device_put``/batch-dispatch failure is retried this
        many times (exponential backoff from ``stream_backoff_s``) before
        surfacing :class:`~repro.core.residency.StreamFailure`.  Each
        absorbed retry increments ``IOStats.retries``, so recovery cost is
        observable.  Ignored when residency='device'.
      stream_backoff_s: initial backoff of the retry ladder, in seconds
        (doubles per attempt).  Ignored when residency='device'.
    """

    backend: str = "scan"
    direction: str = "out"
    chunk_cap: Optional[int] = None
    adaptive_cap: bool = False
    vcap: Optional[int] = None
    ecap: Optional[int] = None
    switch_fraction: Optional[float] = 0.10
    compact_fraction: float = 0.5
    alpha: float = 14.0
    beta: float = 24.0
    tile_order: str = "dest"
    interpret: Optional[bool] = None
    residency: str = "device"
    stream_buffer: int = 16
    stream_retries: int = 3
    stream_backoff_s: float = 0.002

    def __post_init__(self):
        from ..kernels.spmv.order import TILE_ORDERS

        if self.backend not in ("scan", "compact", "blocked", "blocked_compact"):
            raise PolicyError(f"unknown backend {self.backend!r}")
        if self.direction not in ("out", "in", "auto"):
            raise PolicyError(f"unknown direction {self.direction!r}")
        if self.tile_order not in TILE_ORDERS:
            raise PolicyError(
                f"unknown tile_order {self.tile_order!r}; expected one of "
                f"{TILE_ORDERS}"
            )
        if self.residency not in ("device", "host"):
            raise PolicyError(
                f"unknown residency {self.residency!r}; expected 'device' "
                "or 'host'"
            )
        if int(self.stream_buffer) < 1:
            raise PolicyError("stream_buffer must be >= 1")
        if int(self.stream_retries) < 0:
            raise PolicyError("stream_retries must be >= 0")
        if float(self.stream_backoff_s) < 0:
            raise PolicyError("stream_backoff_s must be >= 0")

    def with_(self, **kw) -> "ExecutionPolicy":
        """A copy with the given fields replaced."""
        return dataclasses.replace(self, **kw)


def as_policy(
    policy: Optional[ExecutionPolicy],
    default: Optional[ExecutionPolicy] = None,
    **deprecated,
) -> ExecutionPolicy:
    """Merge an explicit policy with an algorithm's deprecated kwargs.

    ``policy`` wins as the base (falling back to ``default``, then to a
    plain :class:`ExecutionPolicy`); any deprecated kwarg the caller
    actually passed (non-``None``) overrides the corresponding field, so
    pre-policy call sites keep working unchanged.
    """
    base = policy if policy is not None else (default or ExecutionPolicy())
    kw = {k: v for k, v in deprecated.items() if v is not None}
    return dataclasses.replace(base, **kw) if kw else base


def beamer_use_pull(
    frontier_edges: jnp.ndarray,
    unexplored_edges: jnp.ndarray,
    frontier_verts: jnp.ndarray,
    n: int,
    *,
    alpha: float = 14.0,
    beta: float = 24.0,
) -> jnp.ndarray:
    """Beamer's direction heuristic as a traced bool.

    Pull pays when the frontier's out-edge mass dwarfs the unexplored mass
    (``m_f * alpha > m_u`` — most push messages would land on explored
    vertices) AND the frontier is not so narrow that streaming candidate
    in-edges over-fetches (``n_f * beta > n``).  Both boundary cases are
    exercised by ``tests/test_policy.py``.
    """
    mf = frontier_edges.astype(jnp.float32)
    mu = unexplored_edges.astype(jnp.float32)
    nf = frontier_verts.astype(jnp.float32)
    return (mf * alpha > mu) & (nf * beta > float(n))


def _select_blocked(sg: SemGraph, direction: str, reverse: bool):
    """(BlockedGraph, active_on, major_degree) for a (direction, reverse)
    pair, mirroring sem_spmv's gather/key/mask conventions."""
    if direction == "out" and not reverse:
        # push: major = src = tile columns; activity skips source blocks.
        return sg.out_blocked, "src", sg.out_degree
    if direction == "out" and reverse:
        # reverse push (bc backward): y[src] (+)= x[dst]; major = src = the
        # ROWS of the transposed tiles, so activity masks destination-side
        # blocks of the reverse view (its row blocks).
        if sg.out_blocked_rev is None and sg.out_blocked is not None:
            raise ResidencyError(
                "reverse blocked view not built; use "
                "device_graph(..., blocked=True, blocked_reverse=True)"
            )
        return sg.out_blocked_rev, "dst", sg.out_degree
    if direction == "in" and not reverse:
        # pull: y[dst] (+)= x[src] gathering ALL sources; major = dst = the
        # rows of the forward tiles.
        if sg.in_degree is None:
            raise ResidencyError(
                "SemGraph has no in-edge view; pull ('in') blocked dispatch "
                "needs a graph built with its in-CSR"
            )
        return sg.out_blocked, "dst", sg.in_degree
    raise NotImplementedError("blocked backend: direction='in' with reverse")


def _check_blocked_semiring(sr: Semiring, tile_semiring: str,
                            weighted: bool) -> bool:
    """Validate (gather semiring, tile encoding); returns the ``boolean``
    flag (or_and executed as f32 matmul + y>0 threshold).  Shared by the
    device blocked path and the host streaming executor so both residencies
    accept and reject exactly the same combinations."""
    boolean = sr.name == "or_and"
    if boolean:
        if tile_semiring not in ("plus_times", "bool"):
            raise ResidencyError(
                "or_and requires 'plus_times' or 'bool' blocked tiles"
            )
        if tile_semiring == "plus_times" and weighted:
            # Real weights in the tiles would let a zero or cancelling
            # negative weight silently drop an edge from the y>0 threshold,
            # and binarizing here would re-copy the whole tile set every
            # superstep — require the 0/1 view built once up front instead.
            raise ResidencyError(
                "or_and on a weighted graph needs occupancy tiles; build "
                "with device_graph(..., blocked_semiring='bool')"
            )
    elif sr.name != tile_semiring:
        raise ResidencyError(
            f"semiring {sr.name!r} needs blocked tiles built with "
            f"semiring={sr.name!r} (have {tile_semiring!r})"
        )
    return boolean


def _blocked_pre_mask(tile_semiring: str, active_on: str,
                      active: jnp.ndarray, x: jnp.ndarray,
                      boolean: bool) -> jnp.ndarray:
    """The kernel-input x: cast for boolean flows and, on push, mask
    inactive senders with the additive identity so block-granular tiles
    stay row-exact.  Shared across residencies (bitwise parity)."""
    xv = x.astype(jnp.float32) if boolean else x
    if active_on == "src":
        ident = jnp.inf if tile_semiring == "min_plus" else 0.0
        mask = active.reshape((-1,) + (1,) * (xv.ndim - 1))
        xv = jnp.where(mask, xv, jnp.asarray(ident, xv.dtype))
    return xv


def _blocked_post(sr: Semiring, active_on: str, active: jnp.ndarray,
                  y: jnp.ndarray, y_init: Optional[jnp.ndarray],
                  boolean: bool, out_dtype) -> jnp.ndarray:
    """The kernel-output epilogue: boolean threshold, pull/reverse masking
    of inactive major rows, y_init combine, dtype restore.  Shared across
    residencies (bitwise parity)."""
    if boolean:
        y = y > 0
    if active_on == "dst":
        # Pull/reverse: contributions land only on active major rows.
        mask = active.reshape((-1,) + (1,) * (y.ndim - 1))
        base = (
            y_init
            if y_init is not None
            else jnp.full(y.shape, sr.identity, y.dtype)
        )
        y = jnp.where(mask, sr.combine_elem(base.astype(y.dtype), y), base)
    elif y_init is not None:
        y = sr.combine_elem(y_init.astype(y.dtype), y)
    if not boolean:
        y = y.astype(out_dtype)
    return y


def blocked_backend_spmv(
    sg: SemGraph,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    *,
    direction: str = "out",
    reverse: bool = False,
    y_init: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
    compact: bool = False,
    grid_bucket: Optional[int] = None,
    assume_fits: bool = False,
) -> tuple[jnp.ndarray, IOStats]:
    """Row-exact SpMV through the blocked Pallas kernel + unified IOStats.

    ``compact=True`` streams the frontier-compacted (permuted) grid instead
    of the full tile grid — same result bitwise, same IOStats, but skipped
    tiles cost ~zero grid time.  ``grid_bucket`` (static, in tiles) sizes
    that grid to a pow2 bucket under jit; ``assume_fits=True`` skips the
    overflow guard for callers that already proved the live tile count
    fits (see :func:`repro.kernels.spmv.blocked_spmv`).

    Tile skipping is block-granular; exactness is restored by masking the
    gather side (push: inactive sources send the additive identity) or the
    scatter side (pull/reverse: inactive major rows keep ``y_init``).
    Supported semirings: plus_times on 'plus_times' tiles, min_plus on
    'min_plus' tiles, and or_and on unweighted 'plus_times' tiles or on
    'bool' occupancy tiles (which any graph can build — required for
    weighted graphs, where real weights baked into the matmul mass could
    drop a zero/negative-weight edge from the y>0 reachability threshold).
    """
    from ..kernels.spmv import blocked_spmv, tile_byte_size

    bg, active_on, deg = _select_blocked(sg, direction, reverse)
    if bg is None:
        raise ResidencyError(
            "SemGraph has no blocked views; build with "
            "device_graph(..., blocked=True)"
        )

    boolean = _check_blocked_semiring(sr, bg.semiring, sg.w is not None)

    n = sg.n
    xv = _blocked_pre_mask(bg.semiring, active_on, active, x, boolean)

    y, stats = blocked_spmv(bg, xv, active, active_on=active_on,
                            interpret=interpret, compact=compact,
                            grid_bucket=grid_bucket, assume_fits=assume_fits)

    y = _blocked_post(sr, active_on, active, y, y_init, boolean, x.dtype)

    # ---- unified IOStats (same units as the scan path) ----
    # requests: one per active major vertex whose block holds >=1 tile.
    blk = bg.bs if active_on == "src" else bg.bd
    n_blocks = bg.n_src_blocks if active_on == "src" else bg.n_dst_blocks
    bid = bg.sbid if active_on == "src" else bg.dbid
    has_tiles = jnp.zeros(n_blocks, bool).at[bid].set(True)
    ap = jnp.zeros(n_blocks * blk, bool).at[:n].set(active)
    per_block_active = ap.reshape(n_blocks, blk)
    requests = jnp.sum(
        jnp.where(has_tiles[:, None], per_block_active, False).astype(jnp.int32)
    )
    # records/bytes: layout-aware — dense tiles move bd*bs 4-byte f32 slots,
    # 'bool' occupancy tiles ship as bitmaps (1 bit/slot, 1/32 the bytes).
    tile_bytes = tile_byte_size(bg)
    st = IOStats(
        requests=requests,
        records=(stats["tiles_fetched"]
                 * (tile_bytes // EDGE_RECORD_BYTES)).astype(jnp.int32),
        chunks_skipped=stats["tiles_skipped"].astype(jnp.int32),
        messages=jnp.sum(jnp.where(active, deg, 0)).astype(jnp.int32),
        supersteps=jnp.zeros((), jnp.int32),
        bytes_moved=(stats["tiles_fetched"] * tile_bytes).astype(jnp.int32),
        x_fetches=stats["x_fetches"].astype(jnp.int32),
        host_bytes=jnp.zeros((), jnp.int32),
        retries=jnp.zeros((), jnp.int32),
    )
    return y, st


def spmv(
    sg: SemGraph,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    *,
    direction: str = "out",
    y_init: Optional[jnp.ndarray] = None,
    reverse: bool = False,
    backend: str = "scan",
    chunk_cap: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> tuple[jnp.ndarray, IOStats]:
    """Chunked SEM SpMV in the given direction ('out' = push, 'in' = pull).

    ``backend`` selects the multicast execution (see module docstring):
    'scan' streams edge chunks through a lax.scan; 'compact' streams only
    the frontier's chunks through a ``chunk_cap``-length work-list;
    'blocked' streams dense Pallas MXU tiles (requires
    ``device_graph(..., blocked=True)``); 'blocked_compact' streams the
    same tiles on the frontier-compacted grid.  ``chunk_cap`` bounds the
    compact work-list — for 'compact' in chunks (defaults to the full
    chunk count), and for 'blocked_compact' in tiles, where it sizes the
    Pallas grid's pow2 bucket under jit (with an overflow guard, so it is
    always exact).
    """
    if backend in ("blocked", "blocked_compact"):
        compact = backend == "blocked_compact"
        return blocked_backend_spmv(
            sg, x, active, sr, direction=direction, reverse=reverse,
            y_init=y_init, compact=compact, interpret=interpret,
            grid_bucket=chunk_cap if compact else None,
        )
    if backend not in ("scan", "compact"):
        raise PolicyError(f"unknown backend {backend!r}")
    store = sg.out_store if direction == "out" else sg.in_store
    if store is None:
        raise ResidencyError(f"SemGraph has no {direction!r} store")
    if backend == "compact":
        cap = store.num_chunks if chunk_cap is None else chunk_cap
        return compact_spmv(store, x, active, sr, y_init=y_init,
                            reverse=reverse, chunk_cap=cap)
    return sem_spmv(store, x, active, sr, y_init=y_init, reverse=reverse)


# --------------------------------------------------------------------------
# policy-driven dispatch
# --------------------------------------------------------------------------
def _adaptive_compact(store, x, active, sr, y_init, reverse, cap,
                      n_act_chunks):
    """lax.switch over pow2 work-list buckets: each superstep runs the
    smallest compiled compact scan that fits its live-chunk count (the
    two-level density-adaptive cap of the ROADMAP, chosen from the count
    computed on-device in the same superstep — no host round-trip, no
    staleness).  The dispatch already proved ``n_act_chunks <= cap``, so
    the selected bucket always fits and every branch can assume_fits."""
    caps = pow2_buckets(cap)
    idx = bucket_index(n_act_chunks, caps)

    def make(c):
        def branch(_):
            return compact_spmv(store, x, active, sr, y_init=y_init,
                                reverse=reverse, chunk_cap=c,
                                assume_fits=True)
        return branch

    return jax.lax.switch(idx, [make(c) for c in caps], None)


def _multicast(sg, x, active, sr, *, direction, reverse, y_init, pol):
    """Dense-vs-compact dispatch within one backend family.

    With ``pol.chunk_cap`` set, live fetch units are counted (chunks or
    tiles, matching the backend) and a ``lax.cond`` routes the mid-density
    band through the compacted execution; the dense arm streams the full
    schedule.  Results are bitwise identical and IOStats field-for-field
    equal on both arms — compaction changes wall-clock, never accounting.
    """
    backend = pol.backend
    if backend in ("blocked", "blocked_compact"):
        # Resolve the tile view up front: both the capped and uncapped
        # paths must stream the schedule the policy asked for.
        bg, active_on, _ = _select_blocked(sg, direction, reverse)
        if bg is None:
            raise ResidencyError(
                "SemGraph has no blocked views; build with "
                "device_graph(..., blocked=True)"
            )
        have = getattr(bg, "tile_order", "dest")
        if have != pol.tile_order:
            raise ResidencyError(
                f"policy wants tile_order={pol.tile_order!r} but the "
                f"graph's blocked view was built with {have!r}; rebuild "
                "with device_graph(..., tile_order=...) or run through "
                "repro.Graph, which caches one view per order"
            )
    if pol.chunk_cap is None and not (
        pol.adaptive_cap and backend in ("scan", "compact")
    ):
        with jax.named_scope("graphyti.dense"):
            return spmv(sg, x, active, sr, direction=direction,
                        reverse=reverse, y_init=y_init, backend=backend,
                        interpret=pol.interpret)
    if backend in ("blocked", "blocked_compact"):
        always_compact = backend == "blocked_compact"
        from ..kernels.spmv import tile_activity

        T = bg.num_tiles
        cap = max(1, min(int(pol.chunk_cap), T))
        n_act_tiles = jnp.sum(tile_activity(bg, active, active_on))
        use_compact = (n_act_tiles <= cap) & (
            n_act_tiles <= jnp.int32(pol.compact_fraction * T)
        )

        def compact_arm(_):
            with jax.named_scope("graphyti.compact"):
                return blocked_backend_spmv(
                    sg, x, active, sr, direction=direction, reverse=reverse,
                    y_init=y_init, compact=True, interpret=pol.interpret,
                    grid_bucket=cap, assume_fits=True,
                )

        def dense_arm(_):
            with jax.named_scope("graphyti.dense"):
                return blocked_backend_spmv(
                    sg, x, active, sr, direction=direction, reverse=reverse,
                    y_init=y_init, compact=always_compact,
                    interpret=pol.interpret,
                )

        return jax.lax.cond(use_compact, compact_arm, dense_arm, None)

    if backend not in ("scan", "compact"):
        raise PolicyError(f"unknown backend {backend!r}")
    store = sg.out_store if direction == "out" else sg.in_store
    if store is None:
        raise ResidencyError(f"SemGraph has no {direction!r} store")
    C = store.num_chunks
    cap = C if pol.chunk_cap is None else max(1, min(int(pol.chunk_cap), C))
    n_act_chunks = jnp.sum(chunk_activity(store, active).astype(jnp.int32))
    use_compact = (n_act_chunks <= cap) & (
        n_act_chunks <= jnp.int32(pol.compact_fraction * C)
    )

    def compact_arm(_):
        # use_compact already proved the live chunks fit the cap, so skip
        # compact_spmv's own overflow cond (it would trace a dead full scan).
        with jax.named_scope("graphyti.compact"):
            if pol.adaptive_cap:
                return _adaptive_compact(store, x, active, sr, y_init,
                                         reverse, cap, n_act_chunks)
            return compact_spmv(store, x, active, sr, y_init=y_init,
                                reverse=reverse, chunk_cap=cap,
                                assume_fits=True)

    def dense_arm(_):
        with jax.named_scope("graphyti.dense"):
            return sem_spmv(store, x, active, sr, y_init=y_init,
                            reverse=reverse)

    return jax.lax.cond(use_compact, compact_arm, dense_arm, None)


def _adaptive_p2p(sg, x, active, sr, *, direction, y_init, vcap, ecap,
                  n_act, act_edges):
    """lax.switch over pow2 (vcap, ecap) capacity pairs: each superstep's
    sparse arm runs the smallest compiled p2p gather that fits BOTH its
    live vertex count and its live edge mass — the p2p analogue of
    ``_adaptive_compact``'s work-list bucketing, sizing per superstep what
    used to be one static per-graph guess.  The vertex and edge bucket
    ladders are padded to equal length and climbed together on the max of
    the two bucket indices, so every branch satisfies both capacities
    (bucket lists are nondecreasing) with only max(log2 vcap, log2 ecap)
    compiled variants — not their product.  The p2p gather's IOStats are
    capacity-invariant once the frontier fits, so re-bucketing changes
    wall-clock and compile count, never accounting."""
    vbuckets = pow2_buckets(vcap)
    ebuckets = pow2_buckets(ecap)
    k = max(len(vbuckets), len(ebuckets))
    vbuckets = vbuckets + (vbuckets[-1],) * (k - len(vbuckets))
    ebuckets = ebuckets + (ebuckets[-1],) * (k - len(ebuckets))
    idx = jnp.maximum(
        bucket_index(n_act, vbuckets), bucket_index(act_edges, ebuckets)
    )

    def make(vc, ec):
        def branch(_):
            return p2p_spmv(sg, x, active, sr, direction=direction,
                            vcap=vc, ecap=ec, y_init=y_init)
        return branch

    return jax.lax.switch(
        idx, [make(vbuckets[i], ebuckets[i]) for i in range(k)], None
    )


def p2p_caps(pol: ExecutionPolicy, n: int, m: int) -> tuple[int, int, int]:
    """``(vcap, ecap, gate)``: the p2p arm's static capacities and its gate.

    The arm opens only when the frontier's edge mass is at most ``gate =
    int(switch_fraction * m)``, so a ``None`` ``ecap`` resolves to that
    bound (clamped to ``[1, m]``): every edge the gate admits has a slot,
    and no slot is padding the gate rules out.  A ``None`` ``vcap``
    resolves to ``n``, since degree-0 vertices add active rows without
    edge mass.  Both residencies' dispatchers, ``Graph.memory_report`` and
    the coreness program resolve through here, so host and device gather
    with one lane count.  Requires ``pol.switch_fraction`` to be set.
    """
    gate = int(pol.switch_fraction * m)
    vcap = pol.vcap if pol.vcap is not None else int(n)
    ecap = pol.ecap if pol.ecap is not None else max(1, min(gate, int(m)))
    return vcap, ecap, gate


def _dispatch(sg, x, active, sr, *, direction, reverse, y_init, pol):
    """The density three-way (multicast / compact / p2p) for one direction.

    p2p is skipped statically when ``pol.switch_fraction`` is None or the
    flow is reversed (the p2p gather has no reverse form).
    """
    if pol.switch_fraction is None or reverse:
        return _multicast(sg, x, active, sr, direction=direction,
                          reverse=reverse, y_init=y_init, pol=pol)
    deg = sg.out_degree if direction == "out" else sg.in_degree
    vcap, ecap, gate = p2p_caps(pol, sg.n, sg.m)
    act_edges = frontier_edge_mass(deg, active)
    n_act = jnp.sum(active.astype(jnp.int32))
    use_p2p = (
        (act_edges <= jnp.int32(gate))
        & (act_edges <= ecap)
        & (n_act <= vcap)
    )

    def sparse(_):
        # use_p2p proved the frontier fits the static caps, so the
        # adaptive ladder tops out exactly there and every bucket is safe.
        with jax.named_scope("graphyti.p2p"):
            if pol.adaptive_cap:
                return _adaptive_p2p(sg, x, active, sr, direction=direction,
                                     y_init=y_init, vcap=vcap, ecap=ecap,
                                     n_act=n_act, act_edges=act_edges)
            return p2p_spmv(
                sg, x, active, sr, direction=direction, vcap=vcap,
                ecap=ecap, y_init=y_init,
            )

    def not_sparse(_):
        return _multicast(sg, x, active, sr, direction=direction,
                          reverse=reverse, y_init=y_init, pol=pol)

    return jax.lax.cond(use_p2p, sparse, not_sparse, None)


def _pull_available(sg: SemGraph, pol: ExecutionPolicy) -> bool:
    """Static check: can this graph execute the pull arm under ``pol``?"""
    if sg.in_degree is None:
        return False
    if pol.backend in ("blocked", "blocked_compact"):
        if sg.out_blocked is None:
            return False
    elif sg.in_store is None:
        return False
    if pol.switch_fraction is not None and sg.in_indptr is None:
        return False
    return True


def batched_union_frontier(
    sg: SemGraph,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    *,
    unexplored: Optional[jnp.ndarray],
    reverse: bool,
    direction: str,
):
    """Collapse an (n, Q) batched frontier into its 1-D union call.

    Returns ``(x_masked, union_active, union_unexplored, lane_mass)``:
    ``x`` identity-masked per lane (so inactive lanes of a union-fetched
    row contribute nothing), the column-union activity sets that drive the
    fetch/dispatch, and the per-lane-summed edge mass that keeps
    ``IOStats.messages`` equal to the sum of the Q solo runs' logical
    masses.  Shared by :func:`traverse` and the host streaming executor so
    both residencies batch identically.
    """
    xm = sr.mask_lanes(x, active)
    union = jnp.any(active, axis=-1)
    un_union = unexplored
    if unexplored is not None and unexplored.ndim > 1:
        un_union = jnp.any(unexplored, axis=-1)
    # Lane mass counts each query's logical edges on the major side the
    # 1-D path charges: out-edges everywhere except a plain pull dispatch,
    # whose activity set is the destination (in-degree) side.
    plain = reverse or unexplored is None
    if plain and not reverse and direction == "in":
        deg = sg.in_degree
    else:
        deg = sg.out_degree
    mass = frontier_edge_mass(deg, active)
    return xm, union, un_union, mass


def traverse(
    sg: SemGraph,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    *,
    policy: Optional[ExecutionPolicy] = None,
    unexplored: Optional[jnp.ndarray] = None,
    reverse: bool = False,
    y_init: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, IOStats]:
    """The engine's traversal entry point: one superstep, policy-dispatched.

    Semantics: every edge whose source is in the frontier (``active``,
    with ``x`` carrying the frontier's per-lane values) contributes
    ``edge_op(x[src], w)`` combined into ``y[dst]``.

    Without ``unexplored`` this is a plain dispatched SpMV in
    ``policy.direction`` ('auto' degrades to push): ``active`` is the
    activity set of that direction's major vertex, exactly like
    :func:`spmv` — e.g. PageRank-pull passes its activated destinations
    with ``direction='in'``.

    With ``unexplored`` (a bool[n] candidate-receiver set) the call is a
    *frontier-expansion* step and the direction becomes an execution
    choice (paper §4.2: the engine, not the algorithm, owns the I/O
    decision):

      * push ('out') streams the frontier's out-chunks and scatters;
      * pull ('in') masks ``x`` to the frontier, streams only the
        *candidates'* in-chunks, and gathers onto them — rows outside
        ``unexplored`` keep ``y_init`` (they are exactly the rows a
        traversal never reads: already-explored vertices);
      * 'auto' picks per superstep via Beamer's α/β heuristic under a
        ``lax.cond`` (falling back to push when the graph lacks pull
        views).

    Accounting: in frontier-expansion mode ``messages`` is normalized to
    the frontier's logical out-edge mass on every path, so it is
    execution-invariant (levels AND messages of a direction-optimized BFS
    are bitwise-equal to static push); requests/records/bytes_moved report
    the I/O the chosen execution actually did.

    Batched queries: ``active`` (and ``unexplored``) may be (n, Q) — Q
    concurrent traversals amortizing one edge stream.  The engine fetches
    the union of the per-query frontiers with each lane's x identity-
    masked by its own frontier (see the module docstring's Q-axis cost
    model); ``messages`` reports the per-lane sum, everything else the
    union sweep's actual I/O.
    """
    pol = policy if policy is not None else ExecutionPolicy()
    if active.ndim > 1:
        xm, union, un_union, mass = batched_union_frontier(
            sg, x, active, sr, unexplored=unexplored, reverse=reverse,
            direction=pol.direction,
        )
        y, st = traverse(sg, xm, union, sr, policy=pol,
                         unexplored=un_union, reverse=reverse, y_init=y_init)
        return y, st._replace(messages=mass)
    is_host = bool(getattr(sg, "is_host_view", False))
    if pol.residency == "host" or is_host:
        if not is_host:
            raise ResidencyError(
                "residency='host' policy met a device-resident graph: this "
                "SemGraph's edge store already lives in device memory, so "
                "streaming it from host would misreport residency.  Run "
                "through repro.Graph (sessions key views on residency) or "
                "build a host view with repro.core.residency.host_graph()"
            )
        if pol.residency != "host":
            raise ResidencyError(
                "device-residency policy met a host-resident graph view: "
                "its edge store has no device copy to dispatch on.  Use "
                "ExecutionPolicy(residency='host') or build a device view "
                "with device_graph()"
            )
        from .residency import host_traverse

        return host_traverse(sg, x, active, sr, policy=pol,
                             unexplored=unexplored, reverse=reverse,
                             y_init=y_init)
    if reverse or unexplored is None:
        direction = pol.direction if pol.direction in ("out", "in") else "out"
        return _dispatch(sg, x, active, sr, direction=direction,
                         reverse=reverse, y_init=y_init, pol=pol)

    mf = frontier_edge_mass(sg.out_degree, active)
    mode = pol.direction
    if mode != "out" and not _pull_available(sg, pol):
        if mode == "in":
            raise ResidencyError(
                "direction='in' needs the graph's pull views (in-store / "
                "in_degree; blocked backends also need the forward tile "
                "view) — build the graph with its in-CSR"
            )
        mode = "out"  # 'auto' without pull views: push is the only option

    def _push(_):
        with jax.named_scope("graphyti.push"):
            return _dispatch(sg, x, active, sr, direction="out",
                             reverse=False, y_init=y_init, pol=pol)

    if mode == "out":
        y, st = _push(None)
        return y, st._replace(messages=mf)

    # Pull executes the frontier's logical multicast as a gather: x is
    # masked to the frontier (non-frontier sources contribute the
    # identity), and only candidate receivers' in-chunks are streamed.
    mask = active.reshape((-1,) + (1,) * (x.ndim - 1))
    xm = jnp.where(mask, x, jnp.asarray(sr.identity, x.dtype))

    def _pull(_):
        with jax.named_scope("graphyti.pull"):
            return _dispatch(sg, xm, unexplored, sr, direction="in",
                             reverse=False, y_init=y_init, pol=pol)

    if mode == "in":
        y, st = _pull(None)
        return y, st._replace(messages=mf)

    use_pull = beamer_use_pull(
        mf,
        frontier_edge_mass(sg.out_degree, unexplored),
        jnp.sum(active.astype(jnp.int32)),
        sg.n,
        alpha=pol.alpha,
        beta=pol.beta,
    )
    y, st = jax.lax.cond(use_pull, _pull, _push, None)
    return y, st._replace(messages=mf)


def hybrid_spmv(
    sg: SemGraph,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    *,
    direction: str = "out",
    vcap: Optional[int] = None,
    ecap: Optional[int] = None,
    switch_fraction: float = 0.10,
    y_init: Optional[jnp.ndarray] = None,
    backend: str = "scan",
    chunk_cap: Optional[int] = None,
    compact_fraction: float = 0.5,
    policy: Optional[ExecutionPolicy] = None,
    unexplored: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, IOStats]:
    """Density-driven multicast / compact / point-to-point dispatch.

    Pre-policy entry point, kept for compatibility: the loose kwargs are
    folded into an :class:`ExecutionPolicy` and handed to
    :func:`traverse`.  New code should build the policy directly (and get
    direction optimization by setting ``direction='auto'`` and passing
    ``unexplored``).

    ``chunk_cap=None`` (default) preserves the historical two-way
    multicast/p2p switch; with it set the dispatch is three-way (see the
    module docstring's cost model).  Every path reports IOStats in
    identical units, and all paths agree with :func:`flat_spmv` on the
    result.
    """
    if policy is None:
        policy = ExecutionPolicy(
            backend=backend,
            direction=direction,
            chunk_cap=chunk_cap,
            vcap=vcap,
            ecap=ecap,
            switch_fraction=switch_fraction,
            compact_fraction=compact_fraction,
        )
    return traverse(sg, x, active, sr, policy=policy, unexplored=unexplored,
                    y_init=y_init)


def flat_spmv(
    sg: SemGraph,
    x: jnp.ndarray,
    active: jnp.ndarray,
    sr: Semiring,
    *,
    direction: str = "out",
    y_init: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """In-memory baseline: single pass over all m edges, no streaming.

    Uses the flat CSR arrays (no chunk metadata, no activity test). This is
    the igraph/NetworkX-style "everything is in RAM" execution the paper
    compares SEM against.
    """
    n = sg.n
    if direction == "out":
        indptr, indices, w = sg.indptr, sg.indices, sg.w
    else:
        indptr, indices, w = sg.in_indptr, sg.in_indices, sg.in_w
    deg = indptr[1 : n + 1] - indptr[:n]
    # The flat arrays are the direction's own CSR, so the expanded row ids
    # are already the major (frontier) side — src for 'out', dst for 'in' —
    # and the column ids the minor side; no further swapping is needed.
    major = jnp.repeat(jnp.arange(n, dtype=jnp.int32), deg, total_repeat_length=sg.m)
    minor = indices
    # Push ('out') gathers from the active major (src) and scatters to the
    # minor (dst); pull ('in') gathers from the minor (src) and scatters
    # onto the active major (dst).
    gather_idx = minor if direction == "in" else major
    key = major if direction == "in" else minor
    xp = pad_state(x, sr)
    mask = active[major]
    contrib = sr.edge_op(xp[gather_idx], w)
    if contrib.ndim > 1:
        mask_b = mask.reshape((-1,) + (1,) * (contrib.ndim - 1))
    else:
        mask_b = mask
    contrib = jnp.where(mask_b, contrib, jnp.asarray(sr.identity, contrib.dtype))
    keyv = jnp.where(mask, key, n)
    y0 = _pad_y_init(sr, xp, y_init, n)
    return sr.scatter(y0, keyv, contrib)[:n]
