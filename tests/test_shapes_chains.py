"""The façade's shape × residency parity matrix (``shape_matrix.py``) on
high-diameter graphs: a path of 97 vertices (about 97 supersteps) and a
cycle whose 256 edges fill exactly two 128-record chunks."""
import pytest

from shape_matrix import PROGRAMS, RESIDENCIES, ShapeMatrix, check_shape

SHAPES = ("path", "cycle")


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
