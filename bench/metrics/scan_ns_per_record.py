"""Device time of the chunk scan per edge record fetched, in ns.

Source: the profiler trace's device self time under the program's
``graphyti.chunk_scan`` scope (``program_trace.reduce_program``), which
both residencies put on their per-chunk scan, over the traced jobs'
``IOStats.records``.  No reading where the trace has no such scope, or
where ``records`` (int32) could have wrapped: a job whose superstep count
times the padded store size could reach 2**31.
"""

INT32_MAX = 2**31 - 1
SCOPE = "graphyti.chunk_scan"


def read(run):
    scopes = (run.trace or {}).get("device_scopes") or {}
    if SCOPE not in scopes:
        return None
    if any(job.supersteps * run.padded_edges > INT32_MAX
           for job in run.jobs):
        return None
    records = sum(job.records for job in run.jobs)
    if records <= 0:
        return None
    return scopes[SCOPE] * 1e9 / records
