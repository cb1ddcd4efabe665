"""The residency axis: host-resident edge store + streamed supersteps.

Pinned down here:

  * **Bitwise parity** — for BFS and PageRank (push AND pull) on all four
    backends, ``residency='host'`` returns bit-identical values, the same
    superstep count, and field-identical IOStats (``host_bytes`` aside —
    the one residency-sensitive counter) as ``residency='device'``; ditto
    direction='auto' BFS (the Beamer switch must fire identically),
    coreness hybrid/p2p messaging, and multi-source betweenness (the
    reverse-tile flow).  Parity is exercised at ``stream_buffer=2`` too,
    so cross-batch accumulator stitching (chunk order, blocked run
    batching, carry combine) is what's being proven, not a one-batch
    degenerate case.
  * **O(n) device residency** — a host session never builds a device edge
    copy: ``memory_report()`` shows ``device_edge_total == 0`` after a
    full run, with a measured ``peak_stage_bytes`` bounded by TWO stream
    buffers (double buffering's worst case); a device session shows the
    O(m) edge bytes.
  * **Cache correctness** — views are keyed on residency: one HostGraph
    per session, one host tile store per (encoding, reverse, tile_order),
    and no silent fallback from host policy to a device view.
  * **Guards** — host policy × device view and device policy × host view
    each raise the dedicated ValueError; host traversal under ``jax.jit``
    raises (streaming needs concrete frontiers); invalid ``residency`` /
    ``stream_buffer`` values are rejected at policy construction.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from conftest import assert_result_parity
from repro.core import (
    ExecutionPolicy,
    OR_AND,
    device_graph,
    host_graph,
    host_traverse,
    traverse,
)
from repro.graph.generators import rmat

pytestmark = pytest.mark.kernel

BACKENDS = ("scan", "compact", "blocked", "blocked_compact")


@pytest.fixture(scope="module")
def host():
    # Small chunks/tiles: many chunks per superstep, so stream batching
    # and double buffering actually engage.
    return rmat(7, edge_factor=6, seed=3, symmetrize=True)


def sessions(host):
    """A fresh (device session, host session) pair — separate sessions so
    the host one can prove it never built a device view."""
    mk = lambda: repro.Graph(host, chunk_size=128, bd=32, bs=32)
    return mk(), mk()


# ------------------------------------------------------------ parity
class TestBitwiseParity:
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("direction", ("out", "auto"))
    def test_bfs(self, host, backend, direction):
        g_d, g_h = sessions(host)
        pol = ExecutionPolicy(backend=backend, direction=direction)
        rd = g_d.bfs(0, policy=pol)
        rh = g_h.bfs(0, policy=pol.with_(residency="host"))
        assert_result_parity(rd, rh)

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("mode", ("push", "pull"))
    def test_pagerank(self, host, backend, mode):
        g_d, g_h = sessions(host)
        pol = ExecutionPolicy(backend=backend)
        rd = g_d.pagerank(mode=mode, policy=pol, max_iters=20)
        rh = g_h.pagerank(mode=mode, policy=pol.with_(residency="host"),
                          max_iters=20)
        assert_result_parity(rd, rh)

    def test_tiny_stream_buffer(self, host):
        # stream_buffer=2 forces many batches per superstep: cross-batch
        # chunk ordering and the blocked carry-combine are on trial.
        for backend in ("scan", "blocked_compact"):
            g_d, g_h = sessions(host)
            pol = ExecutionPolicy(backend=backend)
            rd = g_d.pagerank(policy=pol, max_iters=15)
            rh = g_h.pagerank(
                policy=pol.with_(residency="host", stream_buffer=2),
                max_iters=15)
            assert_result_parity(rd, rh)

    @pytest.mark.parametrize("messaging", ("hybrid", "p2p"))
    def test_coreness(self, host, messaging):
        g_d, g_h = sessions(host)
        pol = ExecutionPolicy()
        rd = g_d.coreness(messaging=messaging, policy=pol)
        rh = g_h.coreness(messaging=messaging,
                          policy=pol.with_(residency="host"))
        assert_result_parity(rd, rh)

    @pytest.mark.parametrize("backend", ("scan", "blocked_compact"))
    def test_betweenness_multi(self, host, backend):
        g_d, g_h = sessions(host)
        pol = ExecutionPolicy(backend=backend)
        src = jnp.arange(4)
        rd = g_d.betweenness(src, policy=pol)
        rh = g_h.betweenness(src, policy=pol.with_(residency="host"))
        assert_result_parity(rd, rh)

    def test_weighted(self):
        rng = np.random.default_rng(0)
        src = rng.integers(0, 80, 500)
        dst = rng.integers(0, 80, 500)
        w = rng.random(500).astype(np.float32)
        hw = repro.Graph.from_edges(src, dst, weights=w,
                                    symmetrize=True).host
        for backend in ("scan", "blocked"):
            g_d = repro.Graph(hw, chunk_size=128, bd=32, bs=32)
            g_h = repro.Graph(hw, chunk_size=128, bd=32, bs=32)
            pol = ExecutionPolicy(backend=backend)
            rd = g_d.pagerank(policy=pol, max_iters=15)
            rh = g_h.pagerank(policy=pol.with_(residency="host"),
                              max_iters=15)
            assert_result_parity(rd, rh)


# ------------------------------------------------------------ residency
class TestMemoryResidency:
    def test_host_session_keeps_device_edges_at_zero(self, host):
        for backend in ("scan", "blocked_compact"):
            g_h = repro.Graph(host, chunk_size=128, bd=32, bs=32)
            pol = ExecutionPolicy(backend=backend, residency="host",
                                  stream_buffer=4)
            g_h.pagerank(policy=pol, max_iters=10)
            mr = g_h.memory_report(pol)
            assert mr["device_edge_total"] == 0
            assert mr["device_views"] == {}
            assert mr["host_store_bytes"] > 0
            # double buffering: at most TWO staging batches in flight.
            assert 0 < mr["peak_stage_bytes"] <= 2 * mr["stream_buffer_bytes"]

    def test_device_session_shows_o_m_edges(self, host):
        g_d = repro.Graph(host, chunk_size=128, bd=32, bs=32)
        g_d.pagerank(max_iters=3)
        mr = g_d.memory_report()
        # edge-bearing device bytes at least one 8-byte record per edge
        assert mr["device_edge_total"] >= host.m * 8
        assert mr["host_store_bytes"] == 0
        assert mr["peak_stage_bytes"] == 0

    def test_host_store_accounts_tile_views(self, host):
        g_h = repro.Graph(host, chunk_size=128, bd=32, bs=32)
        base = g_h.host_view().store_nbytes
        g_h.bfs(0, policy=ExecutionPolicy(backend="blocked",
                                          residency="host"))
        assert g_h.host_view().store_nbytes > base  # tile store material


# ------------------------------------------------------------ caching
class TestSessionCache:
    def test_host_run_builds_no_device_view(self, host):
        g_h = repro.Graph(host, chunk_size=128, bd=32, bs=32)
        g_h.bfs(0, policy=ExecutionPolicy(backend="blocked_compact",
                                          residency="host"))
        assert g_h._base is None
        assert g_h._tiles == {}

    def test_one_host_view_per_session(self, host):
        g_h = repro.Graph(host, chunk_size=128, bd=32, bs=32)
        pol = ExecutionPolicy(residency="host")
        g_h.bfs(0, policy=pol)
        hv = g_h.host_view()
        g_h.pagerank(policy=pol, max_iters=3)
        assert g_h.host_view() is hv

    def test_one_host_tile_store_per_key(self, host):
        g_h = repro.Graph(host, chunk_size=128, bd=32, bs=32)
        hv = g_h.host_view()
        a = hv.blocked_store("plus_times", reverse=False, tile_order="dest")
        b = hv.blocked_store("plus_times", reverse=False, tile_order="dest")
        assert a is b
        c = hv.blocked_store("min_plus", reverse=False, tile_order="dest")
        assert c is not a
        assert set(hv._blocked) == {
            ("plus_times", False, "dest"), ("min_plus", False, "dest")}

    def test_device_runs_unaffected_by_host_runs(self, host):
        # interleave: device -> host -> device; the device results (from
        # the cached device view) must not change.
        g = repro.Graph(host, chunk_size=128, bd=32, bs=32)
        r1 = g.bfs(0)
        g.bfs(0, policy=ExecutionPolicy(residency="host"))
        r2 = g.bfs(0)
        assert np.array_equal(np.asarray(r1.values), np.asarray(r2.values))
        for a, b in zip(r1.iostats, r2.iostats):
            assert int(a) == int(b)


# ------------------------------------------------------------ guards
class TestGuards:
    def test_policy_validation(self):
        with pytest.raises(ValueError, match="residency"):
            ExecutionPolicy(residency="ssd")
        with pytest.raises(ValueError, match="stream_buffer"):
            ExecutionPolicy(stream_buffer=0)

    def test_host_policy_on_device_graph(self, host):
        sg = device_graph(host, chunk_size=128)
        x = jnp.zeros(sg.n)
        act = jnp.ones(sg.n, bool)
        with pytest.raises(ValueError, match="device-resident graph"):
            traverse(sg, x, act, OR_AND,
                     policy=ExecutionPolicy(residency="host"))

    def test_device_policy_on_host_graph(self, host):
        hg = host_graph(host, chunk_size=128)
        x = jnp.zeros(hg.n)
        act = jnp.ones(hg.n, bool)
        with pytest.raises(ValueError, match="host-resident graph view"):
            traverse(hg, x, act, OR_AND, policy=ExecutionPolicy())

    def test_host_traverse_under_jit(self, host):
        hg = host_graph(host, chunk_size=128)
        pol = ExecutionPolicy(residency="host")

        @jax.jit
        def f(x, act):
            y, _ = host_traverse(hg, x, act, OR_AND, policy=pol)
            return y

        with pytest.raises(ValueError, match="cannot run under jit"):
            f(jnp.zeros(hg.n), jnp.ones(hg.n, bool))

    def test_blocked_triangles_rejected_on_host(self, host):
        g = repro.Graph(host, chunk_size=128, bd=32, bs=32)
        with pytest.raises(ValueError, match="residency='host'"):
            g.triangles(policy=ExecutionPolicy(backend="blocked",
                                               residency="host"))

    def test_traverse_routes_host_view_without_policy_flag(self, host):
        # a host view with a residency='host' policy routes through the
        # streaming engine even via the generic traverse() entry point,
        # and matches the device traverse bitwise.
        sg = device_graph(host, chunk_size=128)
        hg = host_graph(host, chunk_size=128)
        x = jnp.asarray(np.random.default_rng(1).random(host.n),
                        jnp.float32)
        act = jnp.ones(host.n, bool)
        pol = ExecutionPolicy(switch_fraction=None)
        from repro.core import PLUS_TIMES

        yd, std = traverse(sg, x, act, PLUS_TIMES, policy=pol)
        yh, sth = traverse(hg, x, act, PLUS_TIMES,
                           policy=pol.with_(residency="host"))
        assert np.array_equal(np.asarray(yd), np.asarray(yh))
        for name, a, b in zip(std._fields, std, sth):
            if name == "host_bytes":
                continue
            assert int(a) == int(b), name
