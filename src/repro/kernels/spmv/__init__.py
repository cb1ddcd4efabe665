from .ops import (
    BlockedGraph,
    TILE_ORDERS,
    blocked_spmv,
    build_blocked,
    build_blocked_arrays,
    compact_grid_size,
    compact_tile_order,
    default_interpret,
    tile_activity,
    tile_byte_size,
    x_fetch_count,
)
from .kernel import SMEM_TILE_CAP
from .order import curve_bits, hilbert_key, morton_key
from .ref import blocked_spmv_ref

__all__ = [
    "BlockedGraph",
    "SMEM_TILE_CAP",
    "TILE_ORDERS",
    "blocked_spmv",
    "build_blocked",
    "build_blocked_arrays",
    "blocked_spmv_ref",
    "compact_grid_size",
    "compact_tile_order",
    "curve_bits",
    "default_interpret",
    "hilbert_key",
    "morton_key",
    "tile_activity",
    "tile_byte_size",
    "x_fetch_count",
]
