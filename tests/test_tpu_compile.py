"""Compile the blocked SpMV kernels for a described TPU v5e, without a chip.

Interpret mode cannot see what Mosaic refuses (SMEM and VMEM budgets,
tiling), so the main-path kernels are compiled here at the widths of an
RMAT scale-14 tile view: T = 16,382 tiles of 128x128, K = 1 and 128
lanes, both semirings.  The SMEM tile cap is pinned from both sides: the
largest grid compiles, and one step more is refused by the compiler once
the kernel's own guard is lifted.

The chunk scans are compiled at the Graph500 scale-21 BFS state,
``bool[2^21, 1]``: no ``reduce`` or ``reshape`` over the whole
``(n + 1)``-row carry may remain in the compiled HLO (the TPU tiles
``[n + 1, 1]`` unlike the ``[n + 1]`` its scatter wants, so a 2-D carry
is relaid out on every chunk step).
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import OR_AND
from repro.core.residency import _chunk_batch_fn
from repro.core.sem import (
    EdgeChunkStore,
    _pad_y_init,
    flatten_single_lane,
    pad_state,
    sem_spmv,
)
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


N_BFS = 2**21  # Graph500 scale 21
CHUNK = 4096
BUFFER = 16  # ExecutionPolicy.stream_buffer's default


def whole_carry_relayouts(hlo: str, rows: int) -> list:
    """The ``reduce``/``reshape`` instructions whose result has ``rows``
    rows (leading dimension)."""
    pat = re.compile(rf"^\s*(?:ROOT\s+)?%?(\S+) = \w+\[{rows}[,\]]"
                     rf"\S* (reduce|reshape)\(", re.M)
    return [m.group(1) for m in pat.finditer(hlo)]


def test_sem_spmv_single_lane_carry_is_1d(one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    C = 64
    store = EdgeChunkStore(
        major=spec((C, CHUNK), jnp.int32), minor=spec((C, CHUNK), jnp.int32),
        w=None, lo=spec((C,), jnp.int32), hi=spec((C,), jnp.int32),
        n=N_BFS, chunk_size=CHUNK, sorted_by="src")
    fn = jax.jit(lambda st, x, a: sem_spmv(st, x, a, OR_AND))
    hlo = fn.lower(store, spec((N_BFS, 1), jnp.bool_),
                   spec((N_BFS,), jnp.bool_)).compile().as_text()
    assert "scatter" in hlo
    assert whole_carry_relayouts(hlo, N_BFS + 1) == []


def test_host_batch_kernel_single_lane_carry_is_1d(one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def stream_operands(x):  # y and xp as _stream_chunks builds them
        xf, _, _ = flatten_single_lane(x)
        xp = pad_state(xf, OR_AND)
        return _pad_y_init(OR_AND, xp, None, N_BFS), xp

    kern = _chunk_batch_fn(OR_AND, N_BFS, True, False)
    ops = [spec((BUFFER, CHUNK), jnp.int32), spec((BUFFER, CHUNK), jnp.int32),
           spec((BUFFER, CHUNK), jnp.float32), spec((BUFFER,), jnp.bool_)]
    active = spec((N_BFS,), jnp.bool_)
    msgs = spec((), jnp.int32)

    def relayouts(x):
        y, xp = jax.eval_shape(stream_operands, x)
        hlo = kern.lower(spec(y.shape, y.dtype), msgs,
                         spec(xp.shape, xp.dtype), active,
                         *ops).compile().as_text()
        assert "scatter" in hlo
        return whole_carry_relayouts(hlo, N_BFS + 1)

    assert relayouts(jax.ShapeDtypeStruct((N_BFS, 1), jnp.bool_)) == []
    # Control: the same kernel on [n + 1, 1] operands relays out its carry
    # and its gathered x on every chunk step, which the reader above sees.
    y2d = jax.ShapeDtypeStruct((N_BFS + 1, 1), jnp.bool_)
    hlo = kern.lower(spec(y2d.shape, y2d.dtype), msgs,
                     spec(y2d.shape, y2d.dtype), active,
                     *ops).compile().as_text()
    assert len(whole_carry_relayouts(hlo, N_BFS + 1)) >= 3
