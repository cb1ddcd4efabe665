"""Share of the traced window in which the device idled while the host
planned a superstep, in %.

Source: ``idle_under["graphyti.plan"]`` (``program_trace.reduce_program``):
the device-idle time whose gaps fall, at their midpoint, under the
program's ``graphyti.plan`` span as the innermost host span, over the
window.  At most ``device_idle``.  No reading where the program recorded
no plan span.
"""

SPAN = "graphyti.plan"


def read(run):
    trace = run.trace or {}
    if SPAN not in (trace.get("spans") or {}) or trace["window_s"] <= 0:
        return None
    return 100.0 * trace["idle_under"].get(SPAN, 0.0) / trace["window_s"]
