"""Single-lane states scan as 1-D vectors (``sem.flatten_single_lane``).

A ``[n, 1]`` vertex state (one BFS key, one query column) is carried
through the chunk scans as ``[n]`` and given back as ``[n, 1]``.  Pinned
down here, on an RMAT graph, for ``or_and``, ``min_plus`` and
``plus_times`` programs, with residency ``host`` and ``device`` and the
``scan`` and ``compact`` backends:

  * ``values`` keeps its shape ``[n, 1]``;
  * ``values`` is bitwise lane 0 of a K = 2 run whose lanes both hold the
    same seed (that run keeps its two lanes through the scans);
  * ``values`` and every ``IOStats`` field are bitwise those of the same
    K = 1 run with the state left 2-D (the helper stubbed to a
    pass-through);
  * host and device results stay bitwise equal.
"""
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.algs.bfs import BFSProgram
from repro.core import MIN_PLUS, PLUS_TIMES, ExecutionPolicy
from repro.core import residency as residency_mod
from repro.core import sem as sem_mod
from repro.core.sem import flatten_single_lane
from repro.graph.generators import rmat

pytestmark = pytest.mark.kernel

SEED = 5


class HopState(NamedTuple):
    dist: jnp.ndarray  # f32[n, K] hops from each lane's seed
    active: jnp.ndarray  # bool[n, K] improved last superstep


class HopProgram(repro.VertexProgram):
    """Per-lane hop distances over ``min_plus`` (unweighted edges)."""

    semiring = MIN_PLUS

    def init(self, sg, seeds):
        seeds = jnp.asarray(seeds, jnp.int32)
        lanes = jnp.arange(seeds.shape[0])
        dist = jnp.full((sg.n, seeds.shape[0]), jnp.inf, jnp.float32)
        dist = dist.at[seeds, lanes].set(0.0)
        return HopState(dist, dist == 0.0)

    def frontier(self, sg, s):
        return repro.Frontier(x=s.dist + 1.0, active=s.active)

    def apply(self, sg, s, gathered):
        dist = jnp.minimum(s.dist, gathered)
        better = dist < s.dist
        return HopState(dist, better), better

    def finalize(self, sg, s):
        return s.dist


class WalkState(NamedTuple):
    mass: jnp.ndarray  # f32[n, K] random-walk mass per lane
    step: jnp.ndarray


class WalkProgram(repro.VertexProgram):
    """Four steps of a random walk from each lane's seed over
    ``plus_times``: fractional sums, so a change of summation order would
    show in the bits."""

    semiring = PLUS_TIMES
    steps = 4

    def init(self, sg, seeds):
        seeds = jnp.asarray(seeds, jnp.int32)
        lanes = jnp.arange(seeds.shape[0])
        mass = jnp.zeros((sg.n, seeds.shape[0]), jnp.float32)
        return WalkState(mass.at[seeds, lanes].set(1.0),
                         jnp.zeros((), jnp.int32))

    def frontier(self, sg, s):
        deg = jnp.maximum(sg.out_degree, 1).astype(jnp.float32)[:, None]
        return repro.Frontier(x=s.mass / deg, active=s.mass > 0)

    def apply(self, sg, s, gathered):
        return WalkState(gathered, s.step + 1), gathered > 0

    def converged(self, sg, s, activated):
        return s.step >= self.steps

    def max_supersteps(self, sg):
        return self.steps

    def finalize(self, sg, s):
        return s.mass


PROGRAMS = {"or_and": BFSProgram, "min_plus": HopProgram,
            "plus_times": WalkProgram}


@pytest.fixture(scope="module")
def graph():
    return rmat(8, edge_factor=6, seed=3, symmetrize=True)


def run(graph, semiring, residency, backend, seeds):
    """One run on a fresh session (the device driver caches its traced
    loop per graph view, so a fresh view traces the helper anew)."""
    g = repro.Graph(graph, chunk_size=128)
    pol = ExecutionPolicy(backend=backend, residency=residency,
                          switch_fraction=None, stream_buffer=4)
    return g.run(PROGRAMS[semiring](), seeds=jnp.asarray(seeds, jnp.int32),
                 policy=pol)


def stats(res, skip=()):
    return {k: int(v) for k, v in res.iostats._asdict().items()
            if k not in skip}


@pytest.fixture
def unflattened(monkeypatch):
    """Run with single-lane states left 2-D through the scans."""
    def stub(x, y_init=None):
        return x, y_init, lambda y: y

    def enable():
        monkeypatch.setattr(sem_mod, "flatten_single_lane", stub)
        monkeypatch.setattr(residency_mod, "flatten_single_lane", stub)

    return enable


@pytest.mark.parametrize("backend", ["scan", "compact"])
@pytest.mark.parametrize("residency", ["host", "device"])
@pytest.mark.parametrize("semiring", sorted(PROGRAMS))
def test_single_lane_matches_two_lanes_and_2d_scan(graph, semiring,
                                                   residency, backend,
                                                   unflattened):
    one = run(graph, semiring, residency, backend, [SEED])
    two = run(graph, semiring, residency, backend, [SEED, SEED])
    assert one.values.shape == (graph.n, 1)
    assert np.array_equal(np.asarray(one.values)[:, 0],
                          np.asarray(two.values)[:, 0])
    assert int(one.supersteps) == int(two.supersteps)
    unflattened()
    ref = run(graph, semiring, residency, backend, [SEED])
    assert ref.values.shape == (graph.n, 1)
    assert np.array_equal(np.asarray(one.values), np.asarray(ref.values))
    assert int(one.supersteps) == int(ref.supersteps)
    assert stats(one) == stats(ref)


@pytest.mark.parametrize("backend", ["scan", "compact"])
@pytest.mark.parametrize("semiring", sorted(PROGRAMS))
def test_single_lane_host_equals_device(graph, semiring, backend):
    dev = run(graph, semiring, "device", backend, [SEED])
    host = run(graph, semiring, "host", backend, [SEED])
    assert np.array_equal(np.asarray(dev.values), np.asarray(host.values))
    assert int(dev.supersteps) == int(host.supersteps)
    assert stats(dev, skip=("host_bytes",)) == stats(host,
                                                     skip=("host_bytes",))
    assert int(host.iostats.host_bytes) > 0


@pytest.mark.parametrize("shape, flat", [
    ((6,), False), ((6, 1), True), ((6, 1, 1), True), ((6, 2), False),
    ((6, 1, 2), False),
])
def test_flatten_single_lane_decides_on_shape(shape, flat):
    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    y0 = x + 1.0
    xf, yf, restore = flatten_single_lane(x, y0)
    assert xf.shape == ((6,) if flat else shape)
    assert yf.shape == xf.shape
    assert np.array_equal(np.asarray(xf).ravel(), np.asarray(x).ravel())
    assert np.array_equal(np.asarray(yf).ravel(), np.asarray(y0).ravel())
    back = restore(jnp.concatenate([xf, xf[:1]]))  # scans give n + 1 rows
    assert back.shape == (7,) + shape[1:]
    assert flatten_single_lane(x)[1] is None
