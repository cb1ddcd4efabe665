"""The host spans and device scopes the engine leaves in a profiler trace.

Host spans are ``jax.profiler.TraceAnnotation``s and device scopes are
``jax.named_scope``s, so both land in the one ``.xplane.pb`` that
``jax.profiler`` records.  With no trace active a span costs about a
microsecond and a scope nothing at run time (it is op metadata).  Span
names carry no per-call data; counts ride as keyword arguments, always
host values that are already known (no span reads the device).

Host spans (``core/residency.py``, ``core/program.py``,
``core/recovery.py``):

* ``graphyti.superstep`` (``it``) — one iteration of an eager BSP loop
  (``run_program_host``, ``run_program_batched``);
* ``graphyti.segment`` (``stop``) — one re-bind of the device driver's
  traced segment loop;
* ``graphyti.plan`` (``live``, ``units``) — a host executor's numpy plan:
  activity mirror, live ids, batch list; also the p2p density gate; the
  chunk executor's plan adds ``flat``, 1 where its scan carries a
  single-lane state as a 1-D vector (``sem.flatten_single_lane``);
* ``graphyti.stage`` (``bytes``, ``units``) — one staging batch: host
  gather plus ``device_put``, retries included; ``bytes`` is the exact
  count that ``IOStats.host_bytes`` adds before its int32 wrap;
* ``graphyti.enqueue`` — the asynchronous launch of one batch kernel;
* ``graphyti.sync`` — a blocking device-to-host read (:func:`host_read`).

Device scopes: ``graphyti.frontier``, ``.gather``, ``.apply``,
``.activate`` and ``.converged`` (the superstep's phases,
``recovery.superstep``); ``graphyti.push`` and ``.pull`` (``traverse``'s
direction arms); ``graphyti.dense``, ``.compact`` and ``.p2p`` (the density
arms); ``graphyti.chunk_scan`` (the per-chunk scan of ``sem_spmv`` and
``compact_spmv`` and the host batch kernel); ``graphyti.tile_kernel`` (the
blocked Pallas calls).
"""
from __future__ import annotations

import jax
import numpy as np


def host_read(x) -> np.ndarray:
    """``np.asarray(x)``, the blocking device-to-host read of a host loop,
    under the ``graphyti.sync`` span."""
    with jax.profiler.TraceAnnotation("graphyti.sync"):
        return np.asarray(x)
