"""Share of the HBM roofline that a PageRank iteration reaches, in %.

The least bytes one pull iteration must move, whatever implements it: the
source id of every directed edge (``m * 4`` B) and, per vertex, its rank
and degree read and its rank written (``n * 12`` B).  The least time is
those bytes over the chip's HBM bandwidth (``bench/peaks.json``); the share
is that time over the device's busy time per iteration in the trace.
"""


def iteration_bytes(n: int, m: int) -> int:
    return 4 * m + 12 * n


def read(run):
    iters = sum(job.supersteps for job in run.jobs)
    if run.trace is None or iters == 0 or run.trace["busy_s"] <= 0:
        return None
    least_s = iters * iteration_bytes(run.n, run.m) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["busy_s"]
