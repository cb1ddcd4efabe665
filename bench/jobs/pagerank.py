"""PageRank jobs: ``Graph.pagerank(**params)`` with a fixed superstep budget.

Every job is the same call on the same graph.  Work is ``m`` directed
edges per iteration run.  Checked against the Jacobi iterates of the
reference: the widest relative gap of any vertex's rank
(``rank_max_rel_err``).  ``warmup`` and ``trace.params`` override the
parameters of the warm-up job and of the traced jobs.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import reference
from bench.jobs import JobRecord


class Jobs:
    def __init__(self, traffic: dict, graph, unscrambled):
        self.params = dict(traffic["params"])
        self.warmup_params = dict(traffic.get("warmup", {}))
        self.trace_params = dict(traffic.get("trace", {}).get("params", {}))
        self.graph = graph
        if self.params.get("tol", None) != 0.0:
            raise ValueError("pagerank traffic is checked against fixed "
                             "Jacobi iterates, which needs tol = 0")

    def call(self, g, policy, params: dict) -> JobRecord:
        res = g.pagerank(policy=policy, **{**self.params, **params})
        jax.block_until_ready(res.values)
        return JobRecord(0.0, res.values, int(res.supersteps),
                         int(res.iostats.records), None)

    def warmup(self, g, policy) -> None:
        self.call(g, policy, self.warmup_params)

    def run(self, g, policy, index: int, *, traced: bool = False
            ) -> JobRecord:
        return self.call(g, policy, self.trace_params if traced else {})

    def work(self, job: JobRecord) -> float:
        return float(self.graph.m) * job.supersteps

    def component_edges(self, job: JobRecord) -> None:
        return None

    def reference_iterates(self, iters: int, store=reference.keep):
        h = self.graph
        return reference.pagerank_iterates(
            h.in_indptr, h.in_indices, np.diff(h.indptr),
            damping=self.params["damping"], iters=iters, store=store)

    def checks(self, jobs: list, limits: dict) -> tuple[dict, int]:
        limit = limits["rank_max_rel_err"]
        refs = self.reference_iterates(max(j.supersteps for j in jobs))
        errs = [reference.max_rel_err(np.asarray(j.values),
                                      refs[j.supersteps - 1])
                if j.supersteps > 0 else float("inf") for j in jobs]
        return ({"rank_max_rel_err": (max(errs), limit)},
                sum(not e <= limit for e in errs))
