"""Bytes staged from host RAM to the device per second of the window, in
GB/s.

Source: the ``bytes`` argument of the program's ``graphyti.stage`` spans
(``program_trace.reduce_program``): every host-to-device staging batch,
padding included, counted exactly (``IOStats.host_bytes`` is the same
count wrapped to int32), over the traced window.  No reading where the
program staged nothing through such a span.
"""

SPAN = "graphyti.stage"


def read(run):
    spans = (run.trace or {}).get("spans") or {}
    if SPAN not in spans or run.trace["window_s"] <= 0:
        return None
    return spans[SPAN]["args"].get("bytes", 0) / run.trace["window_s"] / 1e9
