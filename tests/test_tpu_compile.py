"""Compile the blocked SpMV kernels for a described TPU v5e, without a chip.

Interpret mode cannot see what Mosaic refuses (SMEM and VMEM budgets,
tiling), so the main-path kernels are compiled here at the widths of an
RMAT scale-14 tile view: T = 16,382 tiles of 128x128, K = 1 and 128
lanes, both semirings.  The SMEM tile cap is pinned from both sides: the
largest grid compiles, and one step more is refused by the compiler once
the kernel's own guard is lifted.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.spmv import kernel as spmv_kernel
from repro.kernels.spmv.kernel import (
    SMEM_TILE_CAP,
    spmv_pallas,
    spmv_pallas_compact,
)

pytestmark = pytest.mark.kernel

T_SCALE14 = 16_382  # tiles of an RMAT scale-14 symmetrized graph
T_SCALE15 = 65_443
N_BLOCKS = 128  # 16,384 vertices / 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lower(one_chip, compact: bool, T: int, K: int, semiring: str):
    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tiles = spec((T, 128, 128), jnp.float32)
    sched = [spec((T,))] * 6
    x = spec((N_BLOCKS, 128, K), jnp.float32)
    if compact:
        def f(tiles, perm, db, sb, first, last, accum, nact, x):
            return spmv_pallas_compact(tiles, perm, db, sb, first, last,
                                       accum, nact, x, N_BLOCKS,
                                       semiring=semiring)
        args = [tiles, *sched, spec((1,)), x]
    else:
        def f(tiles, db, sb, first, last, accum, act, x):
            return spmv_pallas(tiles, db, sb, first, last, accum, act, x,
                               N_BLOCKS, semiring=semiring)
        args = [tiles, *sched, x]
    return jax.jit(f).lower(*args)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
@pytest.mark.parametrize("K", [1, 128])
@pytest.mark.parametrize("compact", [False, True], ids=["full", "compact"])
def test_compiles_at_scale14(one_chip, compact, K, semiring):
    lowered = _lower(one_chip, compact, T_SCALE14, K, semiring)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


@pytest.mark.parametrize("compact", [False, True], ids=["full", "compact"])
def test_scale15_grid_raises_clear_error(one_chip, compact):
    with pytest.raises(ValueError, match=f"SMEM tile cap of {SMEM_TILE_CAP}"):
        _lower(one_chip, compact, T_SCALE15, 1, "plus_times")


@pytest.mark.parametrize("compact", [False, True], ids=["full", "compact"])
def test_cap_is_the_compilers_limit(one_chip, compact, monkeypatch):
    _lower(one_chip, compact, SMEM_TILE_CAP, 1, "plus_times").compile()
    with pytest.raises(ValueError, match="SMEM tile cap"):
        _lower(one_chip, compact, SMEM_TILE_CAP + 1, 1, "plus_times")
    monkeypatch.setattr(spmv_kernel, "SMEM_TILE_CAP", 10**9)
    with pytest.raises(Exception, match="(?i)smem"):
        _lower(one_chip, compact, SMEM_TILE_CAP + 1, 1,
               "plus_times").compile()
