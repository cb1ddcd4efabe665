"""Trace reduction: busy time as a union of device intervals, the idle
share, and idle gaps named by the host span that covers them."""
import pytest

from bench import trace
from bench.trace import Event


def test_union_merges_and_clips():
    got = trace.union([(5, 9), (0, 2), (1, 3), (8, 12), (20, 30)], 1, 25)
    assert got == [(1, 3), (5, 12), (20, 25)]


def test_reduce_events_busy_window_and_gaps():
    host = [Event("bench.window", 100, 200), Event("bench.job", 100, 150),
            Event("plan", 135, 165)]
    dev = {"/device:TPU:0": [Event("fusion", 90, 120), Event("scan", 110, 130),
                             Event("fusion", 170, 180)]}
    r = trace.reduce_events(dev, host, "bench.window")
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)  # [100,130) + [170,180)
    assert r["device_ops"][0] == ["fusion", pytest.approx(30e-9)]
    # Gaps [130, 170) and [180, 200), named by the innermost covering span.
    assert r["idle_gaps"] == [["plan", pytest.approx(40e-9)],
                              ["bench.window", pytest.approx(20e-9)]]


def test_busy_is_averaged_over_devices():
    host = [Event("bench.window", 0, 100)]
    dev = {"/device:TPU:0": [Event("a", 0, 100)],
           "/device:TPU:1": [Event("a", 0, 50)]}
    assert trace.reduce_events(dev, host, "bench.window")["busy_s"] \
        == pytest.approx(75e-9)


def test_reduce_refuses_a_trace_without_window_or_device():
    host = [Event("bench.window", 0, 100)]
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce_events({}, host, "bench.window")
    with pytest.raises(ValueError, match="one host span"):
        trace.reduce_events({"/device:TPU:0": []}, [], "bench.window")


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: three jobs of one small jitted
    program inside the ``bench.window`` span."""
    path = __import__("pathlib").Path(__file__).parent / "data" \
        / "tpu_tiny.xplane.pb"
    devices, host = trace.read_xplane(str(path))
    assert list(devices) == ["/device:TPU:0"]
    assert sum(e.name == "bench.job" for e in host) == 3
    r = trace.reduce_events(devices, host, "bench.window")
    assert r["window_s"] == pytest.approx(0.009940922)
    assert r["busy_s"] == pytest.approx(1.534e-05)
    assert 0 < r["busy_s"] < r["window_s"]
    assert [op[0] for op in r["device_ops"]] == [
        "%fusion", "%copy-start", "%copy-done"]
    gaps = [g[1] for g in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] > 0.004
