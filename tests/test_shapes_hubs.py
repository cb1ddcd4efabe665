"""The façade's shape × residency parity matrix (``shape_matrix.py``) on
skewed degrees: a star whose hub's 299 edges span three 128-record
chunks, and the benchmark's Kronecker shape at scale 8."""
import pytest

from shape_matrix import PROGRAMS, RESIDENCIES, ShapeMatrix, check_shape

SHAPES = ("star", "kron8")


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
