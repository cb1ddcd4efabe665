"""Pallas TPU kernel: blocked semiring SpMV with frontier block skipping.

This is the TPU-native form of the paper's SEM hot loop ("fetch edge list,
combine with neighbor state").  The graph is pre-tiled into dense
``(Bd, Bs)`` edge tiles (see ``ops.build_blocked``); vertex state lives in
``(Bs, K)`` VMEM tiles (K = concurrent lanes — the multi-source dimension of
§4.3/§4.4); each tile update is one MXU matmul:

    y[dst_block] (+)= tile (Bd, Bs)  @  x[src_block] (Bs, K)

SEM mechanics mapped onto Pallas:

  * **Streaming**: the grid walks tiles in the schedule the host built
    (``ops.build_blocked(tile_order=...)`` — destination-sorted or a
    Morton/Hilbert curve over the tile grid) while Pallas double-buffers
    the HBM->VMEM DMA of the next tile behind the current matmul — the
    analogue of SAFS async I/O overlapping compute.  A curve order keeps
    consecutive tiles adjacent in both block coordinates, so the x window
    (and soon after, the same accumulator block) is *reused* instead of
    re-fetched — the GraphMP-style cache-aware schedule.
  * **Chunk-activity skipping** (paper P1, "limit superfluous reads"): the
    per-tile frontier activity bit is scalar-prefetched.  For an inactive
    tile the x-block index map redirects to block 0 (already resident, so
    no new DMA is issued) and ``pl.when`` skips the matmul entirely.
  * **Contention-free reduction** (paper P5, functional constructs): tiles
    of one destination block form contiguous *runs* in the schedule (one
    run per block under 'dest' order, several under a curve order), so the
    accumulator lives in a VMEM scratch tile, is zeroed at ``first`` and
    flushed at ``last`` of each run — no atomics, no message queues.  A
    run whose block was already flushed (``accum=1``) flushes by combining
    into ``y`` (``y_ref += acc`` / ``min``); the block's first run
    overwrites, which is exactly "accumulate into a zero-initialized y"
    without needing an HBM-cleared output buffer.  Non-consecutive output
    revisits rely on the revisited block being re-fetched into the output
    window — exact in interpret mode (every step operates on the real
    buffer); on a physical TPU the 'dest' order (single visit per block)
    remains the safe default.

Semirings: ``plus_times`` runs on the MXU (jnp.dot); ``min_plus`` runs on
the VPU via a broadcast min-plus reduction (same tiling, no MXU analogue).

Grid: 1-D over edge tiles.  Scalar-prefetch operands:
  dbid[T]  destination block id per tile (schedule order)
  sbid[T]  source block id per tile
  first[T] 1 where a tile starts a run of its destination block
  last[T]  1 where a tile ends a run of its destination block
  accum[T] 1 where the run's flush combines into y (an earlier run of the
           same destination block already flushed; always 0 under 'dest')
  act[T]   1 where the frontier intersects the tile's source block

Two grid layouts share the kernel bodies:

  * :func:`spmv_pallas` — the full grid: every tile gets a step; inactive
    steps elide the x DMA (index-map redirect) and the matmul (``pl.when``)
    but still cost a grid step, so a sparse frontier's wall-clock stays
    O(T).
  * :func:`spmv_pallas_compact` — the frontier-compacted grid: active
    tiles are permuted to the grid's front (``perm``, stable, so the
    schedule's run structure is preserved — each surviving run keeps its
    boundary and accumulation order), ``first``/``last``/``accum`` are
    recomputed over the permuted order, and every step past the live count
    (``t >= nact``) redirects all three index maps at the last active tile
    — the tile, x block, and output block are already resident, so tail
    steps issue no DMA and no compute, making a sparse frontier cost
    ~``nact`` real steps.  Callers with a concrete frontier shrink the grid
    itself to the next power of two over ``nact`` (see
    ``ops.blocked_spmv(compact=True)``), so the tail is at most ``nact``
    no-op steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["SMEM_TILE_CAP", "spmv_pallas", "spmv_pallas_compact"]

_NEG = -3.0e38

# Both grids scalar-prefetch six int32[grid] schedule arrays into SMEM (the
# compact grid's seventh, ``nact``, is one element), and SMEM is 1 MiB.
# The largest grid the v5e compiler accepts is 43,008 steps (6 x 4 B x
# 43,008 = 1008 KiB); 43,009 fails with "Ran out of memory in memory space
# smem".  tests/test_tpu_compile.py pins both sides.
SMEM_TILE_CAP = 43_008


def _check_grid_fits(steps: int) -> None:
    """Refuse a compiled grid whose schedule would overflow SMEM, before
    Mosaic does so with an opaque out-of-memory error."""
    if steps > SMEM_TILE_CAP:
        raise ValueError(
            f"blocked SpMV grid of {steps} tiles exceeds the compiled "
            f"kernel's SMEM tile cap of {SMEM_TILE_CAP}: it scalar-prefetches "
            "six int32[tiles] schedule arrays into the TPU's 1 MiB SMEM.  "
            "Use backend='scan' or 'compact' for this graph (splitting the "
            "grid is not implemented)."
        )


def _kernel_plus_times(
    dbid, sbid, first, last, accum, act, tiles_ref, x_ref, y_ref, acc_ref
):
    t = pl.program_id(0)

    @pl.when(first[t] == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(act[t] == 1)
    def _accum():
        # (Bd, Bs) @ (Bs, K) on the MXU, f32 accumulation.
        acc_ref[...] += jnp.dot(
            tiles_ref[0], x_ref[0], preferred_element_type=jnp.float32
        )

    # Flush the run: the block's first run overwrites (the zero-init of the
    # accumulate-on-flush contract), later runs combine into y.
    @pl.when((last[t] == 1) & (accum[t] == 0))
    def _flush():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)

    @pl.when((last[t] == 1) & (accum[t] == 1))
    def _flush_combine():
        y_ref[0] = y_ref[0] + acc_ref[...].astype(y_ref.dtype)


def _kernel_min_plus(
    dbid, sbid, first, last, accum, act, tiles_ref, x_ref, y_ref, acc_ref
):
    t = pl.program_id(0)

    @pl.when(first[t] == 1)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, jnp.inf)

    @pl.when(act[t] == 1)
    def _accum():
        w = tiles_ref[0]  # (Bd, Bs); +inf encodes "no edge"
        x = x_ref[0]  # (Bs, K)
        # min over s of (w[d,s] + x[s,k]) on the VPU.
        cand = jnp.min(w[:, :, None] + x[None, :, :], axis=1)
        acc_ref[...] = jnp.minimum(acc_ref[...], cand)

    @pl.when((last[t] == 1) & (accum[t] == 0))
    def _flush():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)

    @pl.when((last[t] == 1) & (accum[t] == 1))
    def _flush_combine():
        y_ref[0] = jnp.minimum(y_ref[0], acc_ref[...].astype(y_ref.dtype))


def spmv_pallas(
    tiles: jnp.ndarray,  # [T, Bd, Bs] dense edge tiles
    dbid: jnp.ndarray,  # [T] int32, schedule order
    sbid: jnp.ndarray,  # [T] int32
    first: jnp.ndarray,  # [T] int32 0/1 — run start
    last: jnp.ndarray,  # [T] int32 0/1 — run end
    accum: jnp.ndarray,  # [T] int32 0/1 — run flush combines into y
    act: jnp.ndarray,  # [T] int32 0/1 — frontier hits tile's src block
    x_blocks: jnp.ndarray,  # [nSB, Bs, K] vertex state
    n_dst_blocks: int,
    *,
    semiring: str = "plus_times",
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y_blocks [n_dst_blocks, Bd, K] (f32).

    Inactive-tile fetches are elided by redirecting the x-block index map to
    block 0 — an unchanged index means Pallas reuses the resident VMEM block
    instead of issuing a DMA (the kernel-level form of chunk skipping).
    """
    T, Bd, Bs = tiles.shape
    nSB, _, K = x_blocks.shape
    if not interpret:
        _check_grid_fits(T)
    # 'bool' occupancy tiles accumulate 0/1 mass on the plus_times kernel.
    kernel = _kernel_min_plus if semiring == "min_plus" else _kernel_plus_times

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(T,),
        in_specs=[
            pl.BlockSpec(
                (1, Bd, Bs),
                lambda t, dbid, sbid, first, last, accum, act: (t, 0, 0),
            ),
            pl.BlockSpec(
                (1, Bs, K),
                # redirect to block 0 when inactive: no new DMA is issued for
                # a block that is already resident.
                lambda t, dbid, sbid, first, last, accum, act: (
                    act[t] * sbid[t], 0, 0,
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, Bd, K),
            lambda t, dbid, sbid, first, last, accum, act: (dbid[t], 0, 0),
        ),
        scratch_shapes=[pltpu.VMEM((Bd, K), jnp.float32)],
    )

    with jax.named_scope("graphyti.tile_kernel"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_dst_blocks, Bd, K),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(dbid, sbid, first, last, accum, act, tiles, x_blocks)


def _kernel_plus_times_compact(
    perm, dbid, sbid, first, last, accum, nact, tiles_ref, x_ref, y_ref,
    acc_ref
):
    t = pl.program_id(0)

    @pl.when(first[t] == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Every step below the live count is an active tile (that is the whole
    # point of the permutation); tail steps have first == last == 0 and
    # resident-redirected index maps, so they do nothing at all.
    @pl.when(t < nact[0])
    def _accum():
        acc_ref[...] += jnp.dot(
            tiles_ref[0], x_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when((last[t] == 1) & (accum[t] == 0))
    def _flush():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)

    @pl.when((last[t] == 1) & (accum[t] == 1))
    def _flush_combine():
        y_ref[0] = y_ref[0] + acc_ref[...].astype(y_ref.dtype)


def _kernel_min_plus_compact(
    perm, dbid, sbid, first, last, accum, nact, tiles_ref, x_ref, y_ref,
    acc_ref
):
    t = pl.program_id(0)

    @pl.when(first[t] == 1)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, jnp.inf)

    @pl.when(t < nact[0])
    def _accum():
        w = tiles_ref[0]
        x = x_ref[0]
        cand = jnp.min(w[:, :, None] + x[None, :, :], axis=1)
        acc_ref[...] = jnp.minimum(acc_ref[...], cand)

    @pl.when((last[t] == 1) & (accum[t] == 0))
    def _flush():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)

    @pl.when((last[t] == 1) & (accum[t] == 1))
    def _flush_combine():
        y_ref[0] = jnp.minimum(y_ref[0], acc_ref[...].astype(y_ref.dtype))


def spmv_pallas_compact(
    tiles: jnp.ndarray,  # [T, Bd, Bs] dense edge tiles
    perm: jnp.ndarray,  # [G] int32 tile id per grid step (active-compacted)
    dbid: jnp.ndarray,  # [G] int32 dst block per step (permuted order)
    sbid: jnp.ndarray,  # [G] int32 src block per step (permuted order)
    first: jnp.ndarray,  # [G] int32 0/1 — step starts a run (live only)
    last: jnp.ndarray,  # [G] int32 0/1 — step ends a run (live only)
    accum: jnp.ndarray,  # [G] int32 0/1 — run flush combines into y
    nact: jnp.ndarray,  # [1] int32 — number of live steps
    x_blocks: jnp.ndarray,  # [nSB, Bs, K] vertex state
    n_dst_blocks: int,
    *,
    semiring: str = "plus_times",
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y_blocks [n_dst_blocks, Bd, K] (f32), compacted grid.

    The grid length is ``G = len(perm)`` — the caller's (possibly
    size-bucketed) work-list capacity, not the tile count.  Steps
    ``t >= nact[0]`` carry the last live step's tile/x/out coordinates, so
    no DMA is issued and ``pl.when`` skips all compute: a skipped tile costs
    one empty grid step.  Destination blocks none of whose tiles are live
    are never flushed; the caller fills their rows with the semiring
    identity (see ``ops.blocked_spmv``).
    """
    T, Bd, Bs = tiles.shape
    nSB, _, K = x_blocks.shape
    kernel = (
        _kernel_min_plus_compact
        if semiring == "min_plus"
        else _kernel_plus_times_compact
    )
    G = int(perm.shape[0])
    if not interpret:
        _check_grid_fits(G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(G,),
        in_specs=[
            pl.BlockSpec(
                (1, Bd, Bs),
                lambda t, perm, dbid, sbid, first, last, accum, nact: (
                    perm[t], 0, 0,
                ),
            ),
            pl.BlockSpec(
                (1, Bs, K),
                lambda t, perm, dbid, sbid, first, last, accum, nact: (
                    sbid[t], 0, 0,
                ),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, Bd, K),
            lambda t, perm, dbid, sbid, first, last, accum, nact: (
                dbid[t], 0, 0,
            ),
        ),
        scratch_shapes=[pltpu.VMEM((Bd, K), jnp.float32)],
    )

    with jax.named_scope("graphyti.tile_kernel"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_dst_blocks, Bd, K),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(perm, dbid, sbid, first, last, accum, nact, tiles, x_blocks)
