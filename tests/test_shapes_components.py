"""The façade's shape × residency parity matrix (``shape_matrix.py``) on
disconnected graphs: two RMAT islands plus 16 isolated vertices, and a
sparse Erdos-Renyi graph of many small components."""
import pytest

from shape_matrix import PROGRAMS, RESIDENCIES, ShapeMatrix, check_shape

SHAPES = ("islands", "sparse")


@pytest.fixture(scope="module")
def matrix():
    return ShapeMatrix()


@pytest.mark.parametrize("shape", SHAPES)
def test_shape_is_as_named(matrix, shape):
    check_shape(matrix, shape)


@pytest.mark.parametrize("residency", RESIDENCIES)
@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("shape", SHAPES)
def test_shape_parity(matrix, shape, program, residency):
    matrix.check(shape, program, residency)
