"""Edge records fetched per directed edge of the searched components.

Source: the program's ``IOStats.records`` of each search.  Under
``residency='host'`` every fetched record crosses the host link, so this is
the link's work amplification.  ``records`` is int32: a search whose
superstep count times the padded store size could reach 2**31 gives no
reading.
"""

INT32_MAX = 2**31 - 1


def read(run):
    searched = [(job, edges) for job, edges in zip(run.jobs,
                                                   run.component_edges)
                if edges]
    if not searched:
        return None
    for job, _ in searched:
        if job.supersteps * run.padded_edges > INT32_MAX:
            return None
    return (sum(job.records for job, _ in searched)
            / sum(edges for _, edges in searched))
