"""Graph500 BFS jobs: one search key per ``Graph.bfs`` call.

Graph500 draws ``max_keys`` (64) search keys among the vertices with at
least one edge.  They are drawn once, from the traffic's ``key_seed``, in
the graph's unscrambled vertex order, so that every run searches the same
vertices, under the labels its seed drew, in the same order.  Work is the
undirected edges of each searched component (Graph500 TEPS).  Checked
exactly against the reference levels: the number of vertices whose level
differs (``levels_wrong``).  ``warmup`` holds the parameters of the
warm-up search.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import reference
from bench.jobs import JobRecord


class Jobs:
    def __init__(self, traffic: dict, graph, unscrambled):
        p = traffic["params"]
        self.warmup_params = dict(traffic.get("warmup", {}))
        self.graph = graph
        has_edge = np.flatnonzero(np.diff(graph.indptr) >= 1)
        in_order = has_edge[np.argsort(unscrambled[has_edge])]
        pick = np.random.default_rng(p["key_seed"]).choice(
            in_order.size, p["max_keys"], replace=False)
        self.keys = [int(k) for k in in_order[pick]]

    def call(self, g, policy, key: int, params: dict) -> JobRecord:
        res = g.bfs(key, policy=policy, **params)
        jax.block_until_ready(res.values)
        return JobRecord(0.0, res.values, int(res.supersteps),
                         int(res.iostats.records), key)

    def warmup(self, g, policy) -> None:
        self.call(g, policy, self.keys[0], self.warmup_params)

    def run(self, g, policy, index: int, *, traced: bool = False
            ) -> JobRecord:
        return self.call(g, policy, self.keys[index % len(self.keys)], {})

    def work(self, job: JobRecord) -> float:
        return self.component_edges(job) / 2.0

    def component_edges(self, job: JobRecord) -> int:
        return reference.component_edges(self.graph.indptr,
                                         np.asarray(job.values))

    def checks(self, jobs: list, limits: dict) -> tuple[dict, int]:
        h = self.graph
        wrong_total, failed = 0, 0
        for job in jobs:
            levels = np.asarray(job.values)
            if levels.shape != (h.n,):
                wrong = h.n
            else:
                ref = reference.bfs_levels(h.indptr, h.indices, job.key)
                wrong = int(np.sum(levels != ref))
            wrong_total += wrong
            failed += wrong > limits["levels_wrong"]
        return {"levels_wrong": (wrong_total, limits["levels_wrong"])}, failed
