"""Helpers shared by the test modules of ``tests/``."""
import numpy as np


def assert_result_parity(rd, rh):
    """A device-residency result ``rd`` and a host-residency result ``rh``
    of the same call agree bitwise: values, superstep count, and every
    IOStats field except ``host_bytes`` (the one residency-sensitive,
    measured counter), which is positive on the host side only."""
    assert np.array_equal(np.asarray(rd.values), np.asarray(rh.values))
    assert int(rd.supersteps) == int(rh.supersteps)
    for name, a, b in zip(rd.iostats._fields, rd.iostats, rh.iostats):
        if name == "host_bytes":
            continue  # the one residency-sensitive (measured) counter
        assert int(a) == int(b), f"IOStats.{name}: {int(a)} != {int(b)}"
    assert int(rh.iostats.host_bytes) > 0  # the stream actually shipped
    assert int(rd.iostats.host_bytes) == 0
