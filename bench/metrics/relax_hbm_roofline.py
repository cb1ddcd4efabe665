"""Share of the HBM roofline that a shortest-path search's relaxation
reaches, in %.

The least bytes one search must move, whatever implements it: for every
directed edge of the reached component its neighbour id and float32 weight,
each read once, as in Dijkstra (``8`` B per edge), and per vertex its
distance read and written once (``8`` B).  The least time is those bytes
over the chip's HBM bandwidth (``bench/peaks.json``); the share is that
time over the device's busy time per search in the trace.
"""


def search_bytes(n: int, component_edges: int) -> int:
    return 8 * component_edges + 8 * n


def read(run):
    searched = [edges for edges in run.component_edges if edges]
    if run.trace is None or not searched or run.trace["busy_s"] <= 0:
        return None
    least_s = sum(search_bytes(run.n, edges) for edges in searched) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["busy_s"]
