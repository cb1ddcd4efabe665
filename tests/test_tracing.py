"""Profiler spans and device scopes (``repro.core.spans``).

Pinned down here, on the CPU:

  * a host-residency BFS traced with ``jax.profiler`` leaves one
    ``graphyti.superstep`` span per superstep, and its ``graphyti.stage``
    spans' ``bytes`` add up to the bytes of the padded batches of live
    chunks, counted here from the CSR and the BFS levels alone — the
    exact count that ``IOStats.host_bytes`` holds while below 2**31;
  * values and every ``IOStats`` field are bitwise the same with the
    profiler on and off;
  * the device scopes reach the compiled HLO's ``op_name`` metadata of the
    device superstep (through the driver's ``make_jaxpr`` /
    ``jaxpr_as_fun`` re-bind), the chunk scans, the host batch kernel and
    the dispatch arms, also the weighted ``min_plus`` arms of an SSSP
    superstep;
  * the SSSP program's ``improved`` counter is the number of distance
    decreases a plain Bellman-Ford makes.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import jaxpr_as_fun

import repro
from repro.core import (ExecutionPolicy, OR_AND, PLUS_TIMES, device_graph,
                        traverse)
from repro.algs.pagerank import PageRankPullProgram
from repro.algs.sssp import SSSPProgram
from repro.graph import csr
from repro.core.recovery import superstep_body
from repro.core.residency import _chunk_batch_fn
from repro.core.sem import IOStats, compact_spmv, sem_spmv
from repro.graph.generators import rmat

CHUNK = 64
BUFFER = 4
HOST = ExecutionPolicy(switch_fraction=None, residency="host",
                       stream_buffer=BUFFER)


@pytest.fixture(scope="module")
def graph():
    return rmat(9, edge_factor=6, seed=5, symmetrize=True)


def traced(tmp_path, fn):
    """``fn()``'s result and the host events of a trace of it, as
    ``[(name, stats)]`` in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    events = [(e.start_ns, e.name, dict(e.stats)) for p in pd.planes
              if p.name == "/host:CPU" for ln in p.lines for e in ln.events]
    return out, [(name, stats) for _, name, stats in sorted(
        events, key=lambda e: e[0])]


def bfs_levels(g, key):
    level = np.full(g.n, -1)
    level[key] = 0
    frontier = [key]
    while frontier:
        nxt = [int(u) for v in frontier
               for u in g.indices[g.indptr[v]:g.indptr[v + 1]]
               if level[u] < 0]
        nxt = sorted(set(nxt))
        level[nxt] = level[frontier[0]] + 1
        frontier = nxt
    return level


def live_chunks(g, levels, supersteps):
    """Per superstep of a host BFS, the out-store chunks (CHUNK edges in
    CSR order) whose source range holds a frontier vertex."""
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    starts = np.arange(0, g.m, CHUNK)
    lo, hi = src[starts], src[np.minimum(starts + CHUNK, g.m) - 1]
    out = []
    for t in range(supersteps):
        on = np.flatnonzero(levels == t)
        out.append(sum(bool(np.any((on >= a) & (on <= b)))
                       for a, b in zip(lo, hi)))
    return out


def streamed_bytes(g, levels, supersteps):
    """Bytes a host BFS ships: its live chunks, in batches of BUFFER
    chunks padded to full size; a chunk slot is its int32 source and
    destination columns, plus one ``valid`` byte."""
    return sum(-(-live // BUFFER) * BUFFER * (CHUNK * 8 + 1)
               for live in live_chunks(g, levels, supersteps))


def test_host_bfs_spans(graph, tmp_path):
    g = repro.Graph(graph, chunk_size=CHUNK)
    g.bfs(3, policy=HOST)  # compile outside the trace
    res, events = traced(tmp_path, lambda: g.bfs(3, policy=HOST))
    steps = int(res.supersteps)
    supersteps = [s for name, s in events if name == "graphyti.superstep"]
    assert [s["it"] for s in supersteps] == list(range(steps))
    stage = [s for name, s in events if name == "graphyti.stage"]
    staged = sum(s["bytes"] for s in stage)
    assert staged == streamed_bytes(graph, bfs_levels(graph, 3), steps)
    assert staged == int(res.iostats.host_bytes) < 2**31
    assert sum(s["units"] for s in stage) * CHUNK == int(res.iostats.records)
    plans = [s for name, s in events if name == "graphyti.plan"]
    assert len(plans) == steps
    assert all(s["units"] == -(-graph.m // CHUNK) for s in plans)
    live = live_chunks(graph, bfs_levels(graph, 3), steps)
    assert [s["live"] for s in plans] == live
    # One key: the [n, 1] state scans as a 1-D vector in every superstep.
    assert all(s["flat"] == 1 for s in plans)
    names = {name for name, _ in events}
    assert {"graphyti.sync", "graphyti.enqueue"} <= names
    # Two lanes (one key twice, so neither retires early) keep their 2-D
    # carry; the plan's live and units counts are the same.
    g.bfs([3, 3], policy=HOST)
    res2, events2 = traced(tmp_path / "k2", lambda: g.bfs([3, 3], policy=HOST))
    assert int(res2.supersteps) == steps
    plans2 = [s for name, s in events2 if name == "graphyti.plan"]
    assert [s["live"] for s in plans2] == live
    assert all(s["units"] == -(-graph.m // CHUNK) for s in plans2)
    assert all(s["flat"] == 0 for s in plans2)


@pytest.mark.parametrize("residency", ["host", "device"])
@pytest.mark.parametrize("algorithm", ["bfs", "pagerank"])
def test_results_bitwise_equal_with_profiler_on(graph, tmp_path, residency,
                                                algorithm):
    pol = ExecutionPolicy(residency=residency, stream_buffer=BUFFER)
    g = repro.Graph(graph, chunk_size=CHUNK)
    if algorithm == "bfs":
        run = lambda: g.bfs(3, policy=pol)
    else:
        run = lambda: g.pagerank(mode="pull", policy=pol, tol=0.0,
                                 max_iters=3)
    off = run()
    on, events = traced(tmp_path, run)
    assert any(name.startswith("graphyti.") for name, _ in events)
    assert np.array_equal(np.asarray(off.values), np.asarray(on.values))
    assert int(off.supersteps) == int(on.supersteps)
    for name, a, b in zip(IOStats._fields, off.iostats, on.iostats):
        assert int(a) == int(b), name


def op_names(fn, *args) -> str:
    """The ``op_name`` metadata of ``fn``'s compiled HLO, joined."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "\n".join(part.split('"', 1)[0]
                     for part in text.split('op_name="')[1:])


def test_scopes_reach_the_device_superstep(graph):
    sg = device_graph(graph, chunk_size=CHUNK)
    prog = PageRankPullProgram(tol=0.0)
    pol = prog.prepare_policy(sg, ExecutionPolicy())
    body = superstep_body(sg, prog, pol)
    carry = (prog.init(sg, None), IOStats.zero(), jnp.zeros((), jnp.int32),
             jnp.zeros((), bool), jnp.asarray(4, jnp.int32))

    def seg(*c):
        return jax.lax.while_loop(
            lambda c: jnp.logical_and(~c[3], c[2] < c[4]), body, c)

    # The device driver's path: trace once, re-bind the jaxpr.
    flat, _ = jax.tree_util.tree_flatten(carry)
    names = op_names(jaxpr_as_fun(jax.make_jaxpr(seg)(*carry)), *flat)
    for scope in ("frontier", "gather", "apply", "activate", "converged",
                  "dense", "p2p", "chunk_scan"):
        assert f"graphyti.{scope}" in names, scope


def test_scopes_reach_the_chunk_scans_and_the_host_kernel(graph):
    sg = device_graph(graph, chunk_size=CHUNK)
    x = jnp.ones(graph.n, jnp.float32)
    active = jnp.arange(graph.n) < 40
    for fn in (lambda x, a: sem_spmv(sg.out_store, x, a, PLUS_TIMES),
               lambda x, a: compact_spmv(sg.out_store, x, a, PLUS_TIMES,
                                         chunk_cap=4)):
        assert "graphyti.chunk_scan" in op_names(fn, x, active)
    kern = _chunk_batch_fn(PLUS_TIMES, graph.n, True, False)
    z = jnp.zeros((BUFFER, CHUNK), jnp.int32)
    names = op_names(kern, jnp.zeros(graph.n + 1), jnp.zeros((), jnp.int32),
                     jnp.zeros(graph.n + 1), active, z, z,
                     jnp.zeros((BUFFER, CHUNK)), jnp.ones(BUFFER, bool))
    assert "graphyti.chunk_scan" in names


def test_scopes_reach_the_dispatch_arms(graph):
    sg = device_graph(graph, chunk_size=CHUNK)
    x = jnp.ones(graph.n, bool)
    active = jnp.arange(graph.n) < 40
    unexplored = ~active

    def auto(x, a, u):
        return traverse(sg, x, a, OR_AND, unexplored=u,
                        policy=ExecutionPolicy(direction="auto",
                                               chunk_cap=8))

    names = op_names(auto, x, active, unexplored)
    for scope in ("push", "pull", "dense", "compact", "p2p", "chunk_scan"):
        assert f"graphyti.{scope}" in names, scope


def test_scope_reaches_the_tile_kernel(graph):
    small = rmat(6, edge_factor=4, seed=2, symmetrize=True)
    sg = device_graph(small, blocked=True, bd=32, bs=32)
    pol = ExecutionPolicy(backend="blocked", switch_fraction=None,
                          interpret=True)
    names = op_names(
        lambda x, a: traverse(sg, x, a, PLUS_TIMES, policy=pol),
        jnp.ones(small.n, jnp.float32), jnp.arange(small.n) < 8)
    assert "graphyti.tile_kernel" in names


@pytest.fixture(scope="module")
def weighted(graph):
    w = np.random.default_rng(8).random(graph.m, dtype=np.float32)
    return csr.Graph(n=graph.n, indptr=graph.indptr, indices=graph.indices,
                     weights=w, in_indptr=graph.indptr,
                     in_indices=graph.indices, in_weights=w)


def test_sssp_scopes_reach_the_weighted_arms(weighted):
    sg = device_graph(weighted, chunk_size=CHUNK)
    prog = SSSPProgram()
    # The default policy compiles no compacted arm; a chunk cap adds it.
    pol = prog.prepare_policy(sg, ExecutionPolicy(chunk_cap=8))
    body = superstep_body(sg, prog, pol)
    carry = (prog.init(sg, jnp.asarray([3], jnp.int32)), IOStats.zero(),
             jnp.zeros((), jnp.int32), jnp.zeros((), bool),
             jnp.asarray(4, jnp.int32))

    def seg(*c):
        return jax.lax.while_loop(
            lambda c: jnp.logical_and(~c[3], c[2] < c[4]), body, c)

    flat, _ = jax.tree_util.tree_flatten(carry)
    names = op_names(jaxpr_as_fun(jax.make_jaxpr(seg)(*carry)), *flat)
    # The frontier phase only hands the state's fields on: no op of its
    # own carries its scope.
    for scope in ("gather", "apply", "p2p", "compact", "dense",
                  "chunk_scan"):
        assert f"graphyti.{scope}" in names, scope


def test_sssp_improved_counts_the_decreases(weighted):
    """A float32 Bellman-Ford in numpy, relaxing the same frontier in the
    same order, makes the program's decreases one for one."""
    src = np.repeat(np.arange(weighted.n), np.diff(weighted.indptr))
    dist = np.full(weighted.n, np.inf, np.float32)
    dist[3] = 0
    frontier = dist == 0
    decreases = 0
    while frontier.any():
        e = frontier[src]
        relaxed = dist.copy()
        np.minimum.at(relaxed, weighted.indices[e],
                      dist[src[e]] + weighted.weights[e])
        frontier = relaxed < dist
        decreases += int(frontier.sum())
        dist = relaxed
    res = repro.Graph(weighted, chunk_size=CHUNK).sssp(3)
    assert np.array_equal(np.asarray(res.values), dist)
    assert int(res.state.improved) == decreases > weighted.n // 2
