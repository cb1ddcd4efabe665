"""Reduction of a JAX profiler trace to device busy time and idle gaps.

``read_xplane`` flattens the ``.xplane.pb`` file that ``jax.profiler``
writes into plain event lists; ``reduce_events`` computes, inside the
window spanned by one host span:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
* ``window_s``: the window's length;
* ``device_ops``: the ten operation names with the most device time;
* ``idle_gaps``: the ten longest device-idle gaps, each named by the
  innermost host span that covers its midpoint (what the host was doing).

Device planes are the ``/device:TPU:<i>`` planes.  Their operations are the
events of the ``XLA Ops`` line (a plane without that line counts all its
events), named by their HLO instruction (``%while.182``: the event name is
the whole instruction text).  Host spans come from the ``/host:CPU`` plane.
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
TOP = 10


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` file under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def read_xplane(path: str) -> tuple[dict, list]:
    """``({device plane: [Event]}, [host Event])`` from a trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            devices[plane.name] = [
                Event(e.name.split(" = ", 1)[0], e.start_ns, e.end_ns)
                for ln in ops for e in ln.events]
        elif plane.name == HOST_PLANE:
            host.extend(Event(e.name, e.start_ns, e.end_ns)
                        for ln in lines for e in ln.events)
    return devices, host


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals clipped to ``[lo, hi]``."""
    merged: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _innermost(host: list, t: float) -> str:
    best = None
    for ev in host:
        if ev.start_ns <= t <= ev.end_ns and (
                best is None
                or ev.end_ns - ev.start_ns < best.end_ns - best.start_ns):
            best = ev
    return best.name if best is not None else "no host span"


def reduce_events(devices: dict, host: list, window_span: str) -> dict:
    """Busy time, window, top operations and idle gaps in the window of the
    host span named ``window_span``.  Raises when the span or a device
    plane is missing: a run whose trace holds neither has nothing to say."""
    spans = [ev for ev in host if ev.name == window_span]
    if len(spans) != 1:
        raise ValueError(f"expected one host span {window_span!r}, found "
                         f"{len(spans)}")
    if not devices:
        raise ValueError("the trace holds no device plane")
    lo, hi = spans[0].start_ns, spans[0].end_ns
    busy_ns = 0.0
    op_ns: dict = {}
    gaps: list = []
    for events in devices.values():
        merged = union(((e.start_ns, e.end_ns) for e in events), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for e in events:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                op_ns[e.name] = op_ns.get(e.name, 0.0) + d
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_ns / len(devices) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[name, ns * 1e-9] for name, ns in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_innermost(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:TOP]],
    }
