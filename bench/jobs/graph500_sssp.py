"""Graph500 SSSP jobs (kernel 3): one search key per ``Graph.sssp`` call.

The keys are the BFS job kind's: ``max_keys`` (64) vertices with at least
one edge, drawn once from the traffic's ``key_seed`` in the graph's
unscrambled order.  Work is the undirected edges of each reached component
(Graph500 TEPS), the degree sum over the finite distances halved.  Checked
against float64 Dijkstra (``reference_sssp``): the vertices whose
reachability differs (``reach_wrong``) and the widest relative distance
error (``dist_max_rel_err``).
"""
from __future__ import annotations

import jax
import numpy as np
import repro

from bench import reference_sssp
from bench.jobs import JobRecord
from bench.jobs.bfs import Jobs as BFSJobs

if not hasattr(repro.Graph, "sssp"):
    # Refuse before the graph is made, not after minutes of set-up.
    raise ImportError("this repro has no Graph.sssp; the SSSP job kind "
                      "needs it")


class Jobs(BFSJobs):
    def call(self, g, policy, key: int, params: dict) -> JobRecord:
        res = g.sssp(key, policy=policy, **params)
        jax.block_until_ready(res.values)
        return JobRecord(0.0, res.values, int(res.supersteps),
                         int(res.iostats.records), key)

    def component_edges(self, job: JobRecord) -> int:
        return reference_sssp.component_edges(self.graph.indptr,
                                              np.asarray(job.values))

    def checks(self, jobs: list, limits: dict) -> tuple[dict, int]:
        h = self.graph
        matrix = reference_sssp.weighted_csr(h.indptr, h.indices, h.weights)
        refs = {}
        reach_total, err_max, failed = 0, 0.0, 0
        for job in jobs:
            got = np.asarray(job.values)
            if got.shape != (h.n,):
                reach, err = h.n, float("inf")
            else:
                if job.key not in refs:
                    refs[job.key] = reference_sssp.distances(matrix, job.key)
                ref = refs[job.key]
                reach = reference_sssp.reach_wrong(got, ref)
                err = reference_sssp.max_rel_err(got, ref)
            reach_total += reach
            err_max = max(err_max, err)
            failed += (reach > limits["reach_wrong"]
                       or not err <= limits["dist_max_rel_err"])
        return {"reach_wrong": (reach_total, limits["reach_wrong"]),
                "dist_max_rel_err": (err_max, limits["dist_max_rel_err"])
                }, failed
