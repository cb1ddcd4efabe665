"""ExecutionPolicy dispatch invariants: direction optimization changes
WALL-CLOCK and BYTES, never answers and never the logical message count.

Pinned down here:

  * push-vs-pull parity of :func:`repro.core.traverse` on every backend
    and semiring — the pull arm (stream candidates' in-chunks, gather from
    the frontier) must agree with push on every candidate row;
  * the Beamer α/β switch decision at and around both thresholds, and
    that 'auto' actually *takes* the cheaper side (verified through the
    records signature of the executed path);
  * graceful degradation: 'auto' without pull views falls back to push,
    explicit 'in' without pull views raises;
  * density-adaptive pow2 ``chunk_cap`` bucketing: bucket selection is
    minimal and device-side, and the adaptive execution stays bitwise
    equal to the full scan with field-for-field equal IOStats;
  * layout-aware ``IOStats.bytes_moved``: 8 B/record unweighted chunks,
    12 B/record weighted, 4 B/slot f32 tiles, 1 bit/slot bool bitmap
    tiles;
  * end-to-end: direction-optimizing BFS is bitwise-equal (levels AND
    messages) to static push on all four backends.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algs import bfs_multi, coreness, pagerank_push
from repro.core import (
    EDGE_RECORD_BYTES,
    ExecutionPolicy,
    OR_AND,
    PLUS_TIMES,
    as_policy,
    beamer_use_pull,
    bucket_index,
    device_graph,
    flat_spmv,
    frontier_edge_mass,
    hybrid_spmv,
    pow2_buckets,
    sem_spmv,
    spmv,
    traverse,
)
from repro.core.sem import chunk_activity
from repro.graph import from_edges
from repro.graph.generators import erdos_renyi, path_graph, rmat

pytestmark = pytest.mark.kernel

BACKENDS = ("scan", "compact", "blocked", "blocked_compact")


@pytest.fixture(scope="module")
def sg():
    g = erdos_renyi(200, 1500, seed=1)
    return device_graph(g, chunk_size=64, blocked=True, bd=32, bs=32)


def _split(n, k):
    """(frontier = first k vertices, unexplored = the rest)."""
    front = jnp.asarray(np.arange(n) < k)
    return front, ~front


# ------------------------------------------------------ push/pull parity
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("sr_name", ["plus_times", "or_and"])
def test_traverse_pull_matches_push_on_candidates(sg, backend, sr_name):
    sr = {"plus_times": PLUS_TIMES, "or_and": OR_AND}[sr_name]
    rng = np.random.default_rng(3)
    if sr_name == "or_and":
        x = jnp.asarray(rng.random((sg.n, 3)) < 0.4)
    else:
        x = jnp.asarray(rng.integers(0, 64, sg.n).astype(np.float32))
    front, unexp = _split(sg.n, 60)
    pol = ExecutionPolicy(backend=backend, chunk_cap=8, switch_fraction=None)
    y_push, st_push = traverse(sg, x, front, sr, policy=pol, unexplored=unexp)
    y_pull, st_pull = traverse(sg, x, front, sr,
                               policy=pol.with_(direction="in"),
                               unexplored=unexp)
    m = np.asarray(unexp)
    if sr_name == "or_and":
        assert bool(jnp.all(y_push[m] == y_pull[m]))
    else:
        np.testing.assert_allclose(
            np.asarray(y_push)[m], np.asarray(y_pull)[m], atol=1e-4
        )
    # the logical message count is execution-invariant.
    mf = int(frontier_edge_mass(sg.out_degree, front))
    assert int(st_push.messages) == int(st_pull.messages) == mf


@pytest.mark.parametrize("backend", ["scan", "blocked"])
def test_traverse_pull_respects_y_init(sg, backend):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, 32, sg.n).astype(np.float32))
    y0 = jnp.asarray(rng.integers(0, 32, sg.n).astype(np.float32))
    front, unexp = _split(sg.n, 50)
    pol = ExecutionPolicy(backend=backend, switch_fraction=None)
    y_push, _ = traverse(sg, x, front, PLUS_TIMES, policy=pol,
                         unexplored=unexp, y_init=y0)
    y_pull, _ = traverse(sg, x, front, PLUS_TIMES,
                         policy=pol.with_(direction="in"),
                         unexplored=unexp, y_init=y0)
    m = np.asarray(unexp)
    np.testing.assert_allclose(np.asarray(y_push)[m], np.asarray(y_pull)[m],
                               atol=1e-4)
    # rows a traversal never reads (explored) keep y_init on the pull arm.
    np.testing.assert_allclose(np.asarray(y_pull)[~m], np.asarray(y0)[~m])


# ------------------------------------------------- Beamer switch decision
def test_beamer_thresholds_exact_boundaries():
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    # pull needs STRICTLY mf*alpha > mu AND nf*beta > n.
    assert not bool(beamer_use_pull(i32(10), i32(140), i32(50), 100,
                                    alpha=14.0, beta=24.0))  # mf*a == mu
    assert bool(beamer_use_pull(i32(10), i32(139), i32(50), 100,
                                alpha=14.0, beta=24.0))
    assert not bool(beamer_use_pull(i32(10), i32(0), i32(4), 96,
                                    alpha=14.0, beta=24.0))  # nf*b == n
    assert bool(beamer_use_pull(i32(10), i32(0), i32(5), 96,
                                alpha=14.0, beta=24.0))
    # both thresholds failing -> push.
    assert not bool(beamer_use_pull(i32(1), i32(10**6), i32(1), 10**6))


def test_auto_takes_pull_when_unexplored_is_tiny(sg):
    """Huge frontier, few candidates: auto must execute the pull arm —
    its records equal the pull execution's, far below push's."""
    x = jnp.asarray(np.arange(sg.n, dtype=np.float32) % 17)
    front, unexp = _split(sg.n, sg.n - 8)  # unexplored = last 8 vertices
    pol = ExecutionPolicy(chunk_cap=None, switch_fraction=None,
                          direction="auto")
    y_a, st_a = traverse(sg, x, front, PLUS_TIMES, policy=pol,
                         unexplored=unexp)
    _, st_pull = traverse(sg, x, front, PLUS_TIMES,
                          policy=pol.with_(direction="in"), unexplored=unexp)
    _, st_push = traverse(sg, x, front, PLUS_TIMES,
                          policy=pol.with_(direction="out"), unexplored=unexp)
    assert int(st_a.records) == int(st_pull.records)
    assert int(st_pull.records) < int(st_push.records)
    # and the answer still matches push on the candidate rows.
    y_p, _ = traverse(sg, x, front, PLUS_TIMES,
                      policy=pol.with_(direction="out"), unexplored=unexp)
    m = np.asarray(unexp)
    np.testing.assert_allclose(np.asarray(y_a)[m], np.asarray(y_p)[m],
                               atol=1e-4)


def test_auto_takes_push_when_frontier_is_narrow(sg):
    """A 2-vertex frontier fails the beta gate regardless of masses."""
    x = jnp.ones(sg.n, jnp.float32)
    front = jnp.zeros(sg.n, bool).at[0].set(True).at[1].set(True)
    unexp = ~front
    pol = ExecutionPolicy(switch_fraction=None, direction="auto")
    _, st_a = traverse(sg, x, front, PLUS_TIMES, policy=pol, unexplored=unexp)
    _, st_push = traverse(sg, x, front, PLUS_TIMES,
                          policy=pol.with_(direction="out"), unexplored=unexp)
    assert int(st_a.records) == int(st_push.records)


def test_auto_without_pull_views_falls_back_to_push():
    g = erdos_renyi(150, 900, seed=7)
    sg_push_only = device_graph(g, chunk_size=64, pull=False)
    sg_full = device_graph(g, chunk_size=64)
    x = jnp.asarray(np.arange(150, dtype=np.float32))
    front, unexp = _split(150, 140)  # auto WOULD pick pull if it could
    pol = ExecutionPolicy(direction="auto", switch_fraction=None)
    y, st = traverse(sg_push_only, x, front, PLUS_TIMES, policy=pol,
                     unexplored=unexp)
    y_push, st_push = traverse(sg_full, x, front, PLUS_TIMES,
                               policy=pol.with_(direction="out"),
                               unexplored=unexp)
    assert bool(jnp.all(y == y_push))
    assert int(st.records) == int(st_push.records)
    # explicit 'in' on the same graph is a hard error, not a silent push.
    with pytest.raises(ValueError, match="pull views"):
        traverse(sg_push_only, x, front, PLUS_TIMES,
                 policy=pol.with_(direction="in"), unexplored=unexp)


# ------------------------------------------- adaptive chunk_cap bucketing
def test_pow2_bucket_helpers():
    assert pow2_buckets(1) == (1,)
    assert pow2_buckets(8) == (1, 2, 4, 8)
    assert pow2_buckets(6) == (1, 2, 4, 6)
    caps = pow2_buckets(16)
    for count, expect in [(0, 0), (1, 0), (2, 1), (3, 2), (4, 2), (5, 3),
                          (9, 4), (16, 4)]:
        idx = int(bucket_index(jnp.asarray(count, jnp.int32), caps))
        assert idx == expect, (count, idx)
        assert caps[idx] >= max(count, 1)  # selected bucket always fits


@pytest.mark.parametrize("density", [0.0, 0.01, 0.1, 0.5, 1.0])
def test_adaptive_cap_bitwise_equals_scan(sg, density):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(0, 64, sg.n).astype(np.float32))
    act = jnp.asarray(np.arange(sg.n) < int(round(density * sg.n)))
    pol = ExecutionPolicy(backend="scan", chunk_cap=sg.out_store.num_chunks,
                          adaptive_cap=True, switch_fraction=None,
                          compact_fraction=1.0)
    y_a, st_a = traverse(sg, x, act, PLUS_TIMES, policy=pol)
    y_s, st_s = sem_spmv(sg.out_store, x, act, PLUS_TIMES)
    assert bool(jnp.all(y_a == y_s))
    assert all(int(a) == int(b) for a, b in zip(st_a, st_s))


def test_adaptive_cap_under_jit(sg):
    x = jnp.asarray(np.arange(sg.n, dtype=np.float32))
    act = jnp.asarray(np.arange(sg.n) < 20)
    pol = ExecutionPolicy(backend="scan", chunk_cap=32, adaptive_cap=True,
                          switch_fraction=None)
    f = jax.jit(lambda x, a: traverse(sg, x, a, PLUS_TIMES, policy=pol))
    y_j, _ = f(x, act)
    y_s, _ = sem_spmv(sg.out_store, x, act, PLUS_TIMES)
    assert bool(jnp.all(y_j == y_s))


def test_blocked_grid_bucket_overflow_stays_exact(sg):
    """spmv(backend='blocked_compact', chunk_cap=1) with many live tiles:
    the grid bucket's lax.cond must fall back to the full grid."""
    x = jnp.asarray(np.arange(sg.n, dtype=np.float32))
    act = jnp.ones(sg.n, bool)
    f = jax.jit(lambda x, a: spmv(sg, x, a, PLUS_TIMES,
                                  backend="blocked_compact", chunk_cap=1))
    y_c, st_c = f(x, act)
    y_b, st_b = spmv(sg, x, act, PLUS_TIMES, backend="blocked")
    assert bool(jnp.all(y_c == y_b))
    assert all(int(a) == int(b) for a, b in zip(st_c, st_b))


# ------------------------------------------------- layout-aware IOStats
def test_bytes_weighted_vs_unweighted_chunks():
    src = np.array([0, 0, 1, 2, 3]); dst = np.array([1, 2, 3, 0, 1])
    gu = from_edges(src, dst, n=4)
    gw = from_edges(src, dst, n=4, weights=np.ones(5, np.float32))
    act = jnp.ones(4, bool)
    x = jnp.ones(4, jnp.float32)
    _, st_u = spmv(device_graph(gu, chunk_size=4), x, act, PLUS_TIMES)
    _, st_w = spmv(device_graph(gw, chunk_size=4), x, act, PLUS_TIMES)
    assert int(st_u.records) == int(st_w.records)
    assert int(st_u.bytes_moved) == int(st_u.records) * EDGE_RECORD_BYTES
    assert int(st_w.bytes_moved) == int(st_w.records) * (EDGE_RECORD_BYTES + 4)
    assert st_u.bytes() == int(st_u.bytes_moved)


def test_bytes_bool_tiles_ship_as_bitmaps():
    g = erdos_renyi(128, 800, seed=3)
    sg_f32 = device_graph(g, chunk_size=64, blocked=True, bd=32, bs=32)
    sg_bool = device_graph(g, chunk_size=64, blocked=True, bd=32, bs=32,
                           blocked_semiring="bool")
    act = jnp.ones(128, bool)
    x = jnp.asarray(np.random.default_rng(0).random((128, 2)) < 0.3)
    _, st_f = spmv(sg_f32, x, act, OR_AND, backend="blocked")
    _, st_b = spmv(sg_bool, x, act, OR_AND, backend="blocked")
    # same tiles fetched, 1 bit/slot instead of 4 bytes/slot: exactly 1/32.
    assert int(st_f.bytes_moved) == 32 * int(st_b.bytes_moved)
    assert int(st_f.bytes_moved) > 0


# ------------------------------------------------------------ end-to-end
@pytest.fixture(scope="module")
def sg_sym():
    g = rmat(8, edge_factor=8, seed=4, symmetrize=True)
    return device_graph(g, chunk_size=128, blocked=True, bd=32, bs=32)


@pytest.mark.parametrize("backend", BACKENDS)
def test_bfs_direction_modes_bitwise_equal(sg_sym, backend):
    """The acceptance bar: direction-optimizing BFS == static push on
    levels AND IOStats messages, per backend."""
    src = jnp.asarray([0, 3, 11], jnp.int32)
    out = {}
    for mode in ("out", "in", "auto"):
        pol = ExecutionPolicy(backend=backend, direction=mode, chunk_cap=8,
                              switch_fraction=None)
        d, io, it = jax.jit(lambda p=pol: bfs_multi(sg_sym, src, policy=p))()
        out[mode] = (np.asarray(d), int(io.messages), int(it))
    for mode in ("in", "auto"):
        assert (out[mode][0] == out["out"][0]).all(), mode
        assert out[mode][1] == out["out"][1], mode
        assert out[mode][2] == out["out"][2], mode


def test_bfs_adaptive_pulls_fewer_bytes_on_dense_graph(sg_sym):
    """On a low-diameter graph the middle supersteps flip to pull, where
    the tiny unexplored side fits the row-exact p2p gather that the huge
    push frontier cannot — the adaptive run must move strictly fewer
    bytes than static push under the same full dispatch."""
    src = jnp.asarray([0], jnp.int32)
    pols = {m: ExecutionPolicy(direction=m, switch_fraction=0.10)
            for m in ("out", "auto")}
    _, io_push, _ = bfs_multi(sg_sym, src, policy=pols["out"])
    _, io_auto, _ = bfs_multi(sg_sym, src, policy=pols["auto"])
    assert int(io_auto.bytes_moved) < int(io_push.bytes_moved)


def test_algorithms_accept_policy_objects(sg_sym):
    """pagerank/coreness run under an explicit policy and agree with the
    deprecated-kwarg path."""
    pol = ExecutionPolicy(backend="compact", chunk_cap=8)
    r_p, _, it_p = pagerank_push(sg_sym, tol=1e-4, policy=pol)
    r_k, _, it_k = pagerank_push(sg_sym, tol=1e-4, backend="compact",
                                 chunk_cap=8)
    assert int(it_p) == int(it_k)
    np.testing.assert_allclose(np.asarray(r_p), np.asarray(r_k), atol=1e-7)
    c_p, _, _ = coreness(sg_sym, policy=pol)
    c_k, _, _ = coreness(sg_sym, chunk_cap=8)
    assert bool(jnp.all(c_p == c_k))


def test_triangles_policy_routes_to_blocked():
    from repro.algs import count_triangles
    from repro.graph.generators import clique_ladder

    g = clique_ladder(sizes=(6, 10), bridge=1, seed=0)
    ref = count_triangles(g)
    res = count_triangles(g, policy=ExecutionPolicy(backend="blocked"))
    assert res.triangles == ref.triangles
    assert isinstance(res.triangles, int)
    # the MXU path has no comparison/request ledger.
    assert (res.comparisons, res.row_requests, res.records) == (0, 0, 0)


def test_as_policy_merging():
    pol = as_policy(None, ExecutionPolicy(switch_fraction=None),
                    backend="blocked", chunk_cap=4)
    assert pol.backend == "blocked" and pol.chunk_cap == 4
    assert pol.switch_fraction is None
    base = ExecutionPolicy(backend="compact", chunk_cap=16)
    merged = as_policy(base, None, backend=None, chunk_cap=8)
    assert merged.backend == "compact" and merged.chunk_cap == 8
    assert as_policy(base, None) is base
    with pytest.raises(ValueError, match="backend"):
        ExecutionPolicy(backend="nope")
    with pytest.raises(ValueError, match="direction"):
        ExecutionPolicy(direction="sideways")


def test_hybrid_spmv_policy_passthrough(sg):
    x = jnp.asarray(np.arange(sg.n, dtype=np.float32) % 13)
    act = jnp.asarray(np.arange(sg.n) < 30)
    pol = ExecutionPolicy(chunk_cap=8, vcap=sg.n, ecap=sg.m)
    y_p, st_p = hybrid_spmv(sg, x, act, PLUS_TIMES, policy=pol)
    y_k, st_k = hybrid_spmv(sg, x, act, PLUS_TIMES, vcap=sg.n, ecap=sg.m,
                            chunk_cap=8)
    assert bool(jnp.all(y_p == y_k))
    assert all(int(a) == int(b) for a, b in zip(st_p, st_k))
    y_f = flat_spmv(sg, x, act, PLUS_TIMES)
    assert bool(jnp.all(y_p == y_f))


@pytest.mark.parametrize("sf", [0.0, 0.1, 0.125, 1.0, 2.0])
def test_p2p_caps_default_to_the_gate(sf):
    """A ``None`` ecap resolves to the gate's edge bound, clamped to
    [1, m]; the gate is the device formula's ``jnp.int32(sf * m)``; ``None``
    vcap is n; explicit caps pass through."""
    from repro.core.engine import p2p_caps

    for n, m in [(1, 0), (4, 1), (10, 7), (40, 64), (300, 1000)]:
        vcap, ecap, gate = p2p_caps(ExecutionPolicy(switch_fraction=sf), n, m)
        assert gate == int(jnp.int32(sf * m))
        assert ecap == max(1, min(int(sf * m), m))
        assert vcap == n
        assert p2p_caps(ExecutionPolicy(switch_fraction=sf, vcap=3, ecap=5),
                        n, m) == (3, 5, gate)
