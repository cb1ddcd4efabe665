"""The device Kronecker generator, run on the CPU at RMAT scale 10."""
import numpy as np
import pytest

from bench.generators import graph500_kronecker as kronecker

PARAMS = dict(scale=10, edgefactor=16, a=0.57, b=0.19, c=0.19)
GRAPH_SEED = 1
SEED = 2**31 + 17  # past 31 bits, as the benchmark's seeds are


@pytest.fixture(scope="module")
def csr():
    return kronecker.kronecker_csr(GRAPH_SEED, SEED, **PARAMS)


def test_csr_layout(csr):
    indptr, indices, unscrambled = csr
    n = 1 << PARAMS["scale"]
    assert indptr.shape == (n + 1,) and indptr.dtype == np.int64
    assert indices.dtype == np.int32
    assert indptr[0] == 0 and indptr[-1] == indices.shape[0]
    assert indices.min() >= 0 and indices.max() < n
    assert np.array_equal(np.sort(unscrambled), np.arange(n))


def test_rows_sorted_deduplicated_loop_free(csr):
    indptr, indices, _ = csr
    row = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    same_row = row[1:] == row[:-1]
    assert np.all(indices[1:][same_row] > indices[:-1][same_row])
    assert not np.any(indices == row)


def _edge_set(indptr, indices, names=None):
    """Sorted ``src * n + dst`` codes, vertices renamed by ``names``."""
    n = indptr.shape[0] - 1
    row = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    col = indices.astype(np.int64)
    if names is not None:
        row, col = names[row].astype(np.int64), names[col].astype(np.int64)
    return np.sort(row * n + col), np.sort(col * n + row)


def test_symmetric(csr):
    fwd, rev = _edge_set(*csr[:2])
    assert np.array_equal(fwd, rev)


def test_kronecker_degree_skew(csr):
    """Graph500's initiator gives a heavy-tailed degree distribution with
    many isolated vertices, like the repository's host RMAT generator."""
    from repro.graph.generators import rmat

    deg = np.diff(csr[0])
    host = np.diff(rmat(PARAMS["scale"], 16, seed=1, symmetrize=True).indptr)
    assert deg.max() > 10 * deg.mean()
    assert np.sort(deg)[-10:].sum() > 0.1 * deg.sum()
    assert 0.05 < np.mean(deg == 0) < 0.25
    assert abs(deg.sum() / host.sum() - 1.0) < 0.03


def test_seed_relabels_the_same_graph(csr):
    again = kronecker.kronecker_csr(GRAPH_SEED, SEED, **PARAMS)
    assert all(np.array_equal(x, y) for x, y in zip(csr, again))
    other = kronecker.kronecker_csr(GRAPH_SEED, SEED + 2**32, **PARAMS)
    assert not np.array_equal(csr[1][:1000], other[1][:1000])
    # Under their unscrambled ids the two are one graph.
    assert np.array_equal(_edge_set(*csr)[0], _edge_set(*other)[0])
    third = kronecker.kronecker_csr(GRAPH_SEED + 1, SEED, **PARAMS)
    assert not np.array_equal(_edge_set(*csr)[0][:1000],
                              _edge_set(*third)[0][:1000])
    with pytest.raises(ValueError):
        kronecker.seed_key(-1)


def test_generate_serves_the_symmetric_graph():
    config = {"graph_seed": GRAPH_SEED, "scale": 8, "edgefactor": 16,
              "A": 0.57, "B": 0.19, "C": 0.19}
    graph, unscrambled = kronecker.generate(SEED, config)
    assert graph.n == 256 and unscrambled.shape == (256,)
    assert graph.in_indptr is graph.indptr
    assert graph.in_indices is graph.indices
