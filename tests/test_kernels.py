"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.kernel

from repro.graph.generators import cycle_graph, erdos_renyi, rmat, star_graph
from repro.kernels.spmv import blocked_spmv, blocked_spmv_ref, build_blocked
from repro.kernels.spmv.ref import coo_spmv_ref


# --------------------------------------------------------------- spmv
@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
@pytest.mark.parametrize("bd,bs", [(32, 32), (64, 16), (16, 64)])
@pytest.mark.parametrize("k", [1, 3])
def test_spmv_matches_ref(semiring, bd, bs, k):
    g = erdos_renyi(150, 1200, seed=3)
    bg = build_blocked(g, bd=bd, bs=bs, semiring=semiring)
    rng = np.random.default_rng(bd * bs + k)
    shape = (g.n, k) if k > 1 else (g.n,)
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    active = jnp.asarray(rng.random(g.n) < 0.4)
    y, _ = blocked_spmv(bg, x, active, interpret=True)
    y_ref = blocked_spmv_ref(bg, x, active)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("graph_fn", [cycle_graph, star_graph])
def test_spmv_full_frontier_equals_coo(graph_fn):
    """With every vertex active the tile decomposition must equal the plain
    edge-list result (the in-memory ground truth)."""
    g = graph_fn(100)
    bg = build_blocked(g, bd=16, bs=16)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(g.n,)).astype(np.float32))
    y, stats = blocked_spmv(bg, x, None, interpret=True)
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    y_coo = coo_spmv_ref(g.n, jnp.asarray(src), jnp.asarray(g.indices), None, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_coo), atol=1e-4, rtol=1e-4)
    assert int(stats["tiles_skipped"]) == 0


def test_spmv_block_skipping_counts():
    """A frontier confined to one source block must skip every tile whose
    source block differs — the kernel-level chunk-activity elision."""
    g = cycle_graph(256)
    bg = build_blocked(g, bd=32, bs=32)
    active = np.zeros(256, bool)
    active[0:8] = True  # only source block 0
    y, stats = blocked_spmv(bg, jnp.ones(256), jnp.asarray(active), interpret=True)
    sbids = np.asarray(bg.sbid)
    expected = int((sbids == 0).sum())
    assert int(stats["tiles_fetched"]) == expected
    assert int(stats["tiles_skipped"]) == bg.num_tiles - expected
    # skipped tiles contribute nothing
    y_ref = blocked_spmv_ref(bg, jnp.ones(256), jnp.asarray(active))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)


def test_spmv_rmat_pagerank_iteration():
    """One PR-push iteration on a skewed graph: kernel == oracle."""
    g = rmat(8, edge_factor=8, seed=2)
    bg = build_blocked(g, bd=32, bs=32)
    deg = np.maximum(np.asarray(g.out_degree), 1)
    x = jnp.asarray((np.ones(g.n) / deg).astype(np.float32))
    y, _ = blocked_spmv(bg, x, None, interpret=True)
    y_ref = blocked_spmv_ref(bg, x, None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4, rtol=1e-4)
