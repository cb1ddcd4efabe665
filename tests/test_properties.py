"""Property-based tests (hypothesis) on the system's core invariants.

Invariants under test:
  * SEM engine == in-memory engine on any graph/frontier/semiring (the
    chunked, counted, skipping path may never change results).
  * I/O accounting: skipped + fetched == total chunks; skipping is exactly
    frontier-disjointness; records == chunk_size x fetched chunks.
  * Semiring laws on the shipped semirings.
  * PageRank mass conservation; coreness peeling-order invariance.
  * Blocked SpMV tiling == COO ground truth for any (bd, bs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip, don't abort -x runs
from hypothesis import given, settings, strategies as st

from repro.core import device_graph, flat_spmv, sem_spmv
from repro.core.sem import chunk_activity
from repro.core.semiring import MIN_PLUS, OR_AND, PLUS_TIMES
from repro.graph.csr import from_edges
from repro.kernels.spmv import blocked_spmv_ref, build_blocked
from repro.kernels.spmv.ref import coo_spmv_ref

settings.register_profile("ci", deadline=None, max_examples=25)
settings.load_profile("ci")


@st.composite
def graphs(draw, max_n=48, max_m=160):
    n = draw(st.integers(2, max_n))
    m = draw(st.integers(0, max_m))
    src = draw(
        st.lists(st.integers(0, n - 1), min_size=m, max_size=m)
    )
    dst = draw(
        st.lists(st.integers(0, n - 1), min_size=m, max_size=m)
    )
    return from_edges(np.asarray(src, np.int64), np.asarray(dst, np.int64), n=n)


@st.composite
def graph_frontier(draw):
    g = draw(graphs())
    frontier = draw(
        st.lists(st.booleans(), min_size=g.n, max_size=g.n)
    )
    return g, np.asarray(frontier)


@given(graph_frontier(), st.sampled_from(["plus_times", "min_plus"]),
       st.integers(4, 64))
def test_sem_equals_inmem(gf, sr_name, chunk):
    """The SEM chunked/skipping path never changes the result."""
    g, frontier = gf
    sr = {"plus_times": PLUS_TIMES, "min_plus": MIN_PLUS}[sr_name]
    sg = device_graph(g, chunk_size=chunk)
    x = jnp.asarray(np.random.default_rng(0).random(g.n).astype(np.float32))
    active = jnp.asarray(frontier)
    y_sem, io = sem_spmv(sg.out_store, x, active, sr)
    y_flat = flat_spmv(sg, x, active, sr)
    np.testing.assert_allclose(
        np.asarray(y_sem), np.asarray(y_flat), atol=1e-5, rtol=1e-5
    )


@given(graph_frontier(), st.integers(4, 64))
def test_io_accounting_invariants(gf, chunk):
    g, frontier = gf
    sg = device_graph(g, chunk_size=chunk)
    store = sg.out_store
    active = jnp.asarray(frontier)
    x = jnp.ones(g.n)
    _, io = sem_spmv(store, x, active, PLUS_TIMES)
    total = store.num_chunks
    fetched = total - int(io.chunks_skipped)
    # records counted in whole fetched chunks
    assert int(io.records) == fetched * store.chunk_size
    # a chunk is fetched iff the frontier intersects its major range
    act = np.asarray(chunk_activity(store, active))
    assert act.sum() == fetched
    lo, hi = np.asarray(store.lo), np.asarray(store.hi)
    f = np.asarray(frontier)
    for c in range(total):
        if lo[c] >= g.n:  # padding chunk
            assert not act[c]
            continue
        expected = f[lo[c] : hi[c] + 1].any()
        assert act[c] == expected


@given(st.sampled_from([PLUS_TIMES, MIN_PLUS, OR_AND]),
       st.lists(
           # XLA flushes f32 subnormals to zero, so x + 0 == x only holds
           # for normal floats — the identity law is tested over them.
           st.floats(-10, 10, allow_subnormal=False, width=32),
           min_size=3, max_size=3,
       ))
def test_semiring_laws(sr, vals):
    """combine is associative/commutative with the declared identity."""
    a, b, c = [jnp.float32(v) for v in vals]
    if sr.name == "or_and":
        a, b, c = [v > 0 for v in (a, b, c)]
    comb = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[sr.combine]
    ident = jnp.asarray(sr.identity, a.dtype)
    lhs, rhs = comb(a, comb(b, c)), comb(comb(a, b), c)
    if sr.combine == "add":
        # f32 addition is not associative: each side rounds twice, each
        # rounding off by at most 2^-24 of the magnitudes summed, so the
        # sides may differ by 2 * 2^-23 * (|a| + |b| + |c|).  A sum that
        # lands below the smallest normal is flushed to zero (see above),
        # off by under 2^-126 for each of the four additions.
        mags = float(abs(a)) + float(abs(b)) + float(abs(c))
        bound = 2 * 2.0**-23 * mags + 4 * float(np.finfo(np.float32).tiny)
        assert abs(float(lhs) - float(rhs)) <= bound
    else:
        np.testing.assert_allclose(lhs, rhs, rtol=1e-6)
    np.testing.assert_allclose(comb(a, b), comb(b, a), rtol=1e-6)
    np.testing.assert_allclose(comb(a, ident), a, rtol=1e-6)


@given(graphs(max_n=32, max_m=120))
def test_pagerank_mass_conserved(g):
    """Ranks stay a probability-like vector: positive, sum <= 1 + tol (the
    teleport term exactly compensates dangling loss on push)."""
    from repro.algs import pagerank_push

    sg = device_graph(g, chunk_size=16)
    ranks, io, iters = pagerank_push(sg, tol=1e-4, max_iters=200)
    r = np.asarray(ranks)
    assert (r > 0).all()
    assert r.sum() < 1.5


@given(graphs(max_n=28, max_m=100), st.integers(2, 5))
def test_blocked_tiling_equals_coo(g, logbd):
    bd = 1 << logbd
    bg = build_blocked(g, bd=bd, bs=bd)
    x = jnp.asarray(np.random.default_rng(1).random(g.n).astype(np.float32))
    y_tiles = blocked_spmv_ref(bg, x, None)
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    y_coo = coo_spmv_ref(g.n, jnp.asarray(src), jnp.asarray(g.indices), None, x)
    np.testing.assert_allclose(np.asarray(y_tiles), np.asarray(y_coo),
                               atol=1e-4, rtol=1e-4)


@given(graphs(max_n=24, max_m=80))
def test_coreness_invariant(g):
    """Every vertex's core number <= its degree, and the k-core property
    holds: inside the subgraph of {core >= k}, degrees are >= k."""
    from repro.algs import coreness

    gu = from_edges(*g.edges(), n=g.n, symmetrize=True)
    sg = device_graph(gu, chunk_size=16)
    core, _, _ = coreness(sg, max_supersteps=8 * gu.n + 16)
    core = np.asarray(core)
    deg = np.asarray(gu.out_degree)
    assert (core <= deg).all()
    kmax = core.max() if core.size else 0
    for k in np.unique(core):
        members = core >= k
        if members.sum() == 0:
            continue
        src, dst = gu.edges()
        sub_deg = np.zeros(gu.n, np.int64)
        mask = members[src] & members[dst]
        np.add.at(sub_deg, src[mask], 1)
        assert (sub_deg[members] >= k).all()
