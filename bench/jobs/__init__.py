"""Job kinds of the benchmark's traffic, one file each.

A traffic file (``bench/traffic/<name>.json``) names its job kind in
``job``; the harness loads ``bench/jobs/<job>.py`` and builds its ``Jobs``
class from the traffic and the generated graph.  A job is one call of the
``repro.Graph`` façade that ends in ``block_until_ready``; a ``Jobs``
object runs them, tells the work each one did, and checks what they
returned against the numpy references in ``reference.py``:

* ``Jobs(traffic, graph, unscrambled)``: ``graph`` is the host CSR the
  program serves, ``unscrambled`` each vertex label's id before the run's
  seed scrambled the labels (the generator's);
* ``warmup(g, policy)``: one short job that compiles the timed executable;
* ``run(g, policy, index, traced=False)``: job ``index`` of the window, or
  of the traced run, as a :class:`JobRecord`;
* ``work(job)``: the job's work in the unit of the traffic's
  ``rate_metric``;
* ``component_edges(job)``: directed edges searched, or ``None``;
* ``checks(jobs, limits)``: ``({name: (value, limit)}, failed jobs)``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional


class JobRecord(NamedTuple):
    """One finished job, read after the window closes."""

    seconds: float
    values: object  # device array until the window has closed
    supersteps: int
    records: int
    key: Optional[int]  # the search key of a search, None otherwise
