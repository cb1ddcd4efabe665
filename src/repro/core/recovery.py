"""Fault-tolerant BSP: superstep checkpointing, resume-exact runs, and an
injected-failure supervisor.

The paper's setting is long-running SEM analytics — jobs spanning hours
whose O(m) tier lives off-device, exactly the regime where a crash at
superstep 900 of an exact-BC sweep must not cost the whole run.  Because
:func:`~repro.core.program.run_program` is the ONE BSP driver, wiring
recovery here covers all six paper algorithms plus every user
:class:`~repro.core.VertexProgram` at once.  Three pieces:

  * **CheckpointSpec** — a frozen description of the checkpoint cadence.
    ``run_program(..., checkpoint=spec)`` snapshots ``(superstep, frontier
    active mask, program state pytree, accumulated IOStats, finished
    flag)`` every ``every_k`` supersteps through the atomic
    :class:`~repro.checkpoint.CheckpointManager` (tmp+rename, optionally
    async off the hot loop), and ``resume=True`` restores the newest
    complete superstep and continues.

  * **Resume-exactness** — a resumed run is *bitwise-equal* (values, total
    supersteps, full IOStats including ``host_bytes``) to an uninterrupted
    run, on every backend and both residencies.  For the device driver
    this is engineered, not hoped for: the single ``lax.while_loop`` is
    replaced by *segments* of the SAME loop body (the segment boundary is
    one extra ``it < stop`` conjunct in the loop condition, with ``stop``
    threaded through the carry), traced ONCE into a jaxpr and re-bound
    eagerly per segment — the body compiles in the identical while-loop
    codegen context, so every superstep's arithmetic is the device
    driver's bit for bit (see :func:`repro.core.residency._loopify` for
    why a plain ``jax.jit`` would not be).  IOStats resume exactly because
    the accumulated ledger is part of the snapshot: work done between the
    restored checkpoint and the crash is replayed, not double-counted.

  * **Fingerprinting** — every snapshot carries a fingerprint of the
    (graph, policy, program, seeds) identity in its ``extra.json``;
    ``resume=True`` against a directory written by a different run raises
    :class:`CheckpointMismatchError` naming the mismatched component
    instead of silently resuming garbage.

  * **Supervision** — :func:`run_supervised` drives the BSP loop through
    crash injection (``FailurePlan`` / ``DeviceFailure``): the driver
    raises at injected supersteps, the supervisor replays from the newest
    checkpoint, and the final result is gated bitwise against the
    uninterrupted run in ``tests/test_recovery.py``.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import os
import signal
import time
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.extend.core import jaxpr_as_fun
import numpy as np

from ..checkpoint import CheckpointManager, latest_step, load_extra
from .engine import ExecutionPolicy
from .sem import IOStats
from .spans import host_read

__all__ = [
    "CheckpointMismatchError",
    "CheckpointSpec",
    "DeviceFailure",
    "FailurePlan",
    "RecoveryReport",
    "run_fingerprint",
    "run_supervised",
]


class DeviceFailure(RuntimeError):
    """Simulated loss of a device/node during a superstep."""


@dataclasses.dataclass
class FailurePlan:
    """Injected events: {superstep: kind} with kind in 'crash' |
    'sigkill'.  Each event fires once.

    'crash' raises :class:`DeviceFailure` inside the process (the unwind
    still runs — async checkpoint waits, context managers close).
    'sigkill' (interpreted by :func:`maybe_fail`) kills the process with
    an uncatchable signal — no unwind, no flush — modelling the OOM
    killer / ``kill -9`` that multi-process fault tolerance must survive;
    pair it with OS-level workers (``run_workers(processes=...)``) and
    the :func:`~repro.core.workqueue.supervise_workers` chaos harness."""

    events: dict

    def pop(self, step: int) -> Optional[str]:
        return self.events.pop(step, None)


class CheckpointMismatchError(RuntimeError):
    """``resume=True`` met a checkpoint written by a *different* run —
    another graph, policy, program, or seed set.  Restoring it would
    silently produce garbage (same tree structure, wrong trajectory), so
    the mismatch is an error naming the offending component(s)."""


@dataclasses.dataclass(frozen=True)
class CheckpointSpec:
    """How (and how often) a BSP run checkpoints.

    Attributes:
      directory: checkpoint root for this run.  One run per directory —
        the fingerprint guard enforces it on resume.
      every_k: snapshot cadence in supersteps.  Convergence and budget
        exhaustion always snapshot (with ``finished=True``), whatever the
        alignment, so a completed run's final state is always restorable.
      keep: newest complete snapshots retained (disk bound).
      async_save: hand serialization to a background thread (the
        device->host snapshot is the only synchronous part), overlapping
        checkpoint I/O with the next supersteps — the SEM principle
        applied to the recovery tier.  The final (finished) snapshot is
        always written blocking.
      max_shard_bytes: when set, snapshots stream out in fsync'd shards
        of at most this many bytes each (peak staging memory bounded by
        one shard, not by the O(n) state — see
        ``checkpoint/store.save_checkpoint``).
      delta: when True, snapshots skip state pieces whose content hash is
        unchanged since the previous complete step, referencing the step
        that physically stores them instead (slowly-changing states —
        e.g. a BFS distance vector past its wavefront — shrink by the
        unchanged fraction; retention keeps referenced steps alive).
      telemetry: optional mutable dict the driver fills with the
        checkpoint layer's *synchronous* cost — ``sync_s`` (seconds spent
        in snapshot/serialize/wait on the hot path) and ``saves`` (count).
        This is the direct measure of checkpoint overhead: differential
        wall-clock comparisons cannot resolve a few-percent cost under
        multi-tenant CPU jitter, the odometer can.  Shared (accumulated)
        across ``child()`` phases; excluded from equality/repr.
    """

    directory: str | Path
    every_k: int = 8
    keep: int = 3
    async_save: bool = True
    max_shard_bytes: Optional[int] = None
    delta: bool = False
    telemetry: Optional[dict] = dataclasses.field(
        default=None, compare=False, repr=False)

    def __post_init__(self):
        if int(self.every_k) < 1:
            raise ValueError("every_k must be >= 1")
        if int(self.keep) < 1:
            raise ValueError("keep must be >= 1")
        if self.max_shard_bytes is not None and int(self.max_shard_bytes) < 1:
            raise ValueError("max_shard_bytes must be >= 1 (or None)")

    def child(self, name: str) -> "CheckpointSpec":
        """A sub-spec rooted at ``directory/name`` — multi-phase drivers
        (betweenness forward/backward, per-source queue shards) give each
        phase its own fingerprinted subdirectory."""
        return dataclasses.replace(self, directory=Path(self.directory) / name)


@dataclasses.dataclass
class RecoveryReport:
    """What :func:`run_supervised` lived through."""

    restarts: int = 0
    resumed_steps: list = dataclasses.field(default_factory=list)
    log: list = dataclasses.field(default_factory=list)


# --------------------------------------------------------------------------
# fingerprinting
# --------------------------------------------------------------------------
def _sha(*parts: bytes) -> str:
    h = hashlib.sha1()
    for p in parts:
        h.update(p)
    return h.hexdigest()


def run_fingerprint(sg, prog, pol: ExecutionPolicy, seeds) -> dict:
    """Identity of a BSP run, per component (so a mismatch can say WHICH
    of graph/policy/program/seeds differs).  Graph identity is the degree
    vectors plus (n, m) — O(n) to hash, and any edge-set change moves it
    with overwhelming probability; policy/program identity is their full
    config repr (both are flat dataclass-style objects)."""
    gparts = [np.int64(sg.n).tobytes(), np.int64(sg.m).tobytes(),
              np.asarray(sg.out_degree).tobytes()]
    in_deg = getattr(sg, "in_degree", None)
    if in_deg is not None:
        gparts.append(np.asarray(in_deg).tobytes())
    sparts = []
    for leaf in jax.tree_util.tree_leaves(seeds):
        a = np.asarray(leaf)
        sparts += [str(a.dtype).encode(), np.asarray(a.shape).tobytes(),
                   a.tobytes()]
    return {
        "graph": _sha(*gparts),
        "policy": _sha(repr(pol).encode()),
        "program": _sha(
            type(prog).__module__.encode(),
            type(prog).__qualname__.encode(),
            repr(sorted(prog.__dict__.items())).encode(),
        ),
        "seeds": _sha(*sparts) if sparts else "none",
    }


# --------------------------------------------------------------------------
# checkpoint context (shared by the device and host drivers)
# --------------------------------------------------------------------------
class _CheckpointCtx:
    """One run's checkpoint channel: manager + fingerprint + snapshot
    schema.  The snapshot tree is ``{finished, frontier, io, it, state}``
    — a fixed structure for any one (program, graph) pair, so restore
    targets rebuild from ``prog.init`` alone."""

    def __init__(self, spec: CheckpointSpec, fp: dict):
        self.spec = spec
        self.fp = fp
        self.mgr = CheckpointManager(
            spec.directory, keep=spec.keep,
            max_shard_bytes=spec.max_shard_bytes, delta=spec.delta,
            telemetry=spec.telemetry)
        if spec.telemetry is not None:
            spec.telemetry.setdefault("sync_s", 0.0)
            spec.telemetry.setdefault("saves", 0)

    def due(self, it: int, finished: bool) -> bool:
        return finished or (it % self.spec.every_k == 0 and it > 0)

    def _clock(self, t0: float) -> None:
        if self.spec.telemetry is not None:
            self.spec.telemetry["sync_s"] += time.perf_counter() - t0

    def save(self, it: int, finished: bool, state, io: IOStats,
             frontier_active) -> None:
        t0 = time.perf_counter()
        tree = {
            "finished": np.asarray(bool(finished)),
            "frontier": frontier_active,
            "io": io,
            "it": np.asarray(int(it), np.int32),
            "state": state,
        }
        extra = dict(self.fp, superstep=int(it), finished=bool(finished))
        self.mgr.save(int(it), tree,
                      blocking=bool(finished) or not self.spec.async_save,
                      extra=extra)
        if self.spec.telemetry is not None:
            self.spec.telemetry["saves"] += 1
        self._clock(t0)

    def try_restore(self, sg, state_template):
        """Newest complete snapshot -> (state, io, it, finished), or None
        when the directory holds none (fresh start).  The fingerprint is
        checked BEFORE any array is touched."""
        step = latest_step(self.spec.directory)
        if step is None:
            return None
        extra = load_extra(self.spec.directory, step) or {}
        bad = [k for k in ("graph", "policy", "program", "seeds")
               if extra.get(k) != self.fp[k]]
        if bad:
            raise CheckpointMismatchError(
                f"checkpoint at {self.spec.directory} (step {step}) was "
                f"written by a different run: {', '.join(bad)} "
                f"fingerprint(s) differ.  Resuming it would silently "
                f"produce garbage; point `checkpoint` at a fresh directory "
                f"or pass resume=False to start over."
            )
        target = {
            "finished": jnp.zeros((), bool),
            "frontier": jnp.zeros(sg.n, bool),
            "io": IOStats.zero(),
            "it": jnp.zeros((), jnp.int32),
            "state": state_template,
        }
        tree, _ = self.mgr.restore(target)
        return (tree["state"], tree["io"], int(tree["it"]),
                bool(tree["finished"]))

    def wait(self) -> None:
        t0 = time.perf_counter()
        self.mgr.wait()
        self._clock(t0)


def maybe_fail(plan: Optional[FailurePlan], it: int) -> None:
    """Raise the injected :class:`DeviceFailure` scheduled for superstep
    ``it`` (fires once; the surviving plan is what the supervisor replays
    with).  The shared injection point of both BSP drivers.

    Kind ``'sigkill'`` does not raise — it kills the *process* with an
    uncatchable SIGKILL, exactly what an OOM kill or a ``kill -9`` does to
    a real worker.  No unwind runs: whatever the checkpoint layer had not
    yet published is lost, which is the failure mode the durable queue's
    heartbeat/reap path and the chaos harness exist to survive."""
    if plan is None:
        return
    kind = plan.pop(it)
    if kind is None:
        return
    if kind == "sigkill":
        os.kill(os.getpid(), signal.SIGKILL)
    raise DeviceFailure(f"injected at superstep {it}")


def _next_planned(plan: Optional[FailurePlan], it: int) -> Optional[int]:
    if plan is None:
        return None
    pending = [s for s in plan.events if s >= it]
    return min(pending) if pending else None


def _assert_concrete(tree, what: str) -> None:
    if any(isinstance(l, jax.core.Tracer)
           for l in jax.tree_util.tree_leaves(tree)):
        raise ValueError(
            f"checkpointing cannot run under jit: the driver snapshots "
            f"concrete {what} to disk between supersteps.  Call "
            f"run_program(checkpoint=...) eagerly (outside jax.jit)."
        )


# --------------------------------------------------------------------------
# the checkpointed device driver
# --------------------------------------------------------------------------
_SEG_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_SEG_CACHE_SIZE = 8


def _segment_fn(sg, prog, pol):
    """Segment runner for ``(sg, prog config, pol)``, cached across runs
    (a checkpointed run, its killed replays, and its resumes all re-bind
    the same traced loop instead of re-compiling).  Keyed by ``id(sg)``
    — safe from id reuse because the cached closure holds a strong
    reference to ``sg``, so a cached graph's id cannot be recycled; the
    LRU bound keeps retired graphs from accumulating."""
    try:
        key = (id(sg), type(prog),
               tuple(sorted(prog.__dict__.items())), pol)
        hit = _SEG_CACHE.get(key)
        if hit is None:
            hit = _SEG_CACHE[key] = _build_segment_fn(sg, prog, pol)
            while len(_SEG_CACHE) > _SEG_CACHE_SIZE:
                _SEG_CACHE.popitem(last=False)
        else:
            _SEG_CACHE.move_to_end(key)
        return hit
    except TypeError:  # unhashable program config: run uncached
        return _build_segment_fn(sg, prog, pol)


def superstep(sg, prog, pol, state, io: IOStats, converged=None):
    """THE BSP superstep, traced: frontier, gather, apply, activate,
    IOStats accumulation, convergence test.  Returns ``(state, io, done)``
    with ``done = converged(sg, state, activated)`` (default
    ``prog.converged``; the batched driver passes ``prog.converged_cols``).

    Each phase runs under its device scope (``graphyti.frontier``,
    ``.gather``, ``.apply``, ``.activate``, ``.converged``), so every device
    driver that traces a superstep names its ops alike in a profile."""
    with jax.named_scope("graphyti.frontier"):
        fr = prog.frontier(sg, state)
    with jax.named_scope("graphyti.gather"):
        gathered, st = prog.gather(sg, state, fr, pol)
    with jax.named_scope("graphyti.apply"):
        state, activated = prog.apply(sg, state, gathered)
    with jax.named_scope("graphyti.activate"):
        state, st_act = prog.activate(sg, state, pol)
    io = io + st
    if st_act is not None:  # static: the program either has the hook or not
        io = io + st_act
    io = io._replace(supersteps=io.supersteps + 1)
    with jax.named_scope("graphyti.converged"):
        done = (converged or prog.converged)(sg, state, activated)
    return state, io, done


def superstep_body(sg, prog, pol):
    """:func:`superstep` as a carry -> carry function.

    Both consumers trace exactly this function: :func:`_build_segment_fn`
    wraps it in the segment ``lax.while_loop`` the device driver executes,
    and :func:`repro.analysis.analyze` traces it into the jaxpr the static
    rules walk.  That sharing is the analyzer's soundness argument: the
    jaxpr it inspects IS the loop body that runs, not a re-derivation.

    The carry is ``(state, io, it, done, stop)`` — the segment machinery's
    layout (``done``/``stop`` ride the carry so the surrounding while-loop
    condition can read them).
    """

    def body(carry):
        state, io, it, _, stop = carry
        state, io, done = superstep(sg, prog, pol, state, io)
        return state, io, it + 1, done, stop

    return body


def _build_segment_fn(sg, prog, pol):
    """The device driver's superstep body, wrapped as a *segment*: the
    same ``lax.while_loop`` with one extra ``it < stop`` conjunct in the
    condition (``stop`` rides the carry).  Traced once into a jaxpr and
    re-bound eagerly per segment — identical while-loop-body codegen to
    the uninterrupted driver, at sub-millisecond re-dispatch
    (cf. :func:`repro.core.residency._loopify`)."""

    body = superstep_body(sg, prog, pol)

    def seg(state, io, it, done, stop):
        return jax.lax.while_loop(
            lambda c: jnp.logical_and(~c[3], c[2] < c[4]), body,
            (state, io, it, done, stop),
        )

    cache: dict = {}

    def call(*args):
        flat, treedef = jax.tree_util.tree_flatten(args)
        # Strip weak types: prog.init's python-scalar-derived leaves are
        # weak, the segment's outputs are strong, and a weak->strong aval
        # flip between segment 1 and 2 would recompile the whole loop
        # (same dtype, same HLO — only the dispatch cache key differs).
        flat = [jnp.asarray(a, jnp.result_type(a)) for a in flat]
        sig = (treedef,
               tuple((jnp.shape(a), jnp.result_type(a)) for a in flat))
        hit = cache.get(sig)
        if hit is None:
            jaxpr, out_shape = jax.make_jaxpr(seg, return_shape=True)(*args)
            hit = (jaxpr_as_fun(jaxpr),
                   jax.tree_util.tree_structure(out_shape))
            cache[sig] = hit
        run_jaxpr, out_tree = hit
        return jax.tree_util.tree_unflatten(out_tree, run_jaxpr(*flat))

    return call


def run_program_checkpointed(
    sg,
    prog,
    policy: Optional[ExecutionPolicy] = None,
    *,
    seeds=None,
    max_supersteps: Optional[int] = None,
    checkpoint: Optional[CheckpointSpec] = None,
    resume: bool = False,
    _plan: Optional[FailurePlan] = None,
):
    """:func:`~repro.core.program.run_program` with recovery wired in —
    reached through its ``checkpoint=`` keyword, never called directly by
    user code.  Host residency delegates to the (already eager) host
    driver, which shares :class:`_CheckpointCtx`/:func:`maybe_fail`."""
    from .program import ProgramResult

    pol = policy if policy is not None else prog.default_policy
    pol = pol if pol is not None else ExecutionPolicy()
    if pol.residency == "host" or getattr(sg, "is_host_view", False):
        from .residency import run_program_host

        return run_program_host(sg, prog, pol, seeds=seeds,
                                max_supersteps=max_supersteps,
                                checkpoint=checkpoint, resume=resume,
                                _plan=_plan)
    pol = prog.prepare_policy(sg, pol)
    state = prog.init(sg, seeds)
    _assert_concrete(state, "program state")
    budget = int(max_supersteps if max_supersteps is not None
                 else prog.max_supersteps(sg))

    ctx = (_CheckpointCtx(checkpoint, run_fingerprint(sg, prog, pol, seeds))
           if checkpoint is not None else None)
    io = IOStats.zero()
    it = 0
    done = (bool(host_read(prog.converged(sg, state, None)))
            if prog.check_initial_convergence else False)
    if resume and ctx is not None:
        hit = ctx.try_restore(sg, state)
        if hit is not None:
            state, io, it, finished = hit
            if finished:
                return ProgramResult(prog.finalize(sg, state),
                                     jnp.asarray(it, jnp.int32), io, state)
            done = False  # an unfinished snapshot is mid-loop by definition

    seg = _segment_fn(sg, prog, pol)
    try:
        while not done and it < budget:
            maybe_fail(_plan, it)
            stop = budget
            if ctx is not None:
                stop = min(stop, (it // ctx.spec.every_k + 1)
                           * ctx.spec.every_k)
            nf = _next_planned(_plan, it + 1)
            if nf is not None:
                stop = min(stop, nf)
            with jax.profiler.TraceAnnotation("graphyti.segment", stop=stop):
                state, io, it_a, done_a, _ = seg(
                    state, io, jnp.asarray(it, jnp.int32),
                    jnp.zeros((), bool), jnp.asarray(stop, jnp.int32),
                )
            it, done = int(host_read(it_a)), bool(host_read(done_a))
            finished = done or it >= budget
            if ctx is not None and ctx.due(it, finished):
                act = prog.frontier(sg, state).active
                if act.ndim > 1:  # batched lanes: snapshot the 1-D union
                    act = jnp.any(act, axis=-1)
                ctx.save(it, finished, state, io, act)
    except BaseException:
        if ctx is not None:
            ctx.wait()  # drain any in-flight async save before unwinding
        raise
    if ctx is not None:
        if it == 0:  # zero-superstep runs still leave a restorable record
            ctx.save(0, True, state, io, jnp.zeros(sg.n, bool))
        ctx.wait()
    return ProgramResult(prog.finalize(sg, state), jnp.asarray(it, jnp.int32),
                         io, state)


# --------------------------------------------------------------------------
# the supervisor
# --------------------------------------------------------------------------
def run_supervised(
    sg,
    prog,
    policy: Optional[ExecutionPolicy] = None,
    *,
    seeds=None,
    max_supersteps: Optional[int] = None,
    checkpoint: CheckpointSpec,
    plan: Optional[FailurePlan] = None,
    max_restarts: int = 16,
):
    """Drive a BSP run to completion through injected failures.

    Each :class:`DeviceFailure` (from ``plan``, or a real one surfacing
    out of the driver) triggers a replay from the newest complete
    checkpoint; the run's final :class:`~repro.core.ProgramResult` is
    bitwise-identical to an uninterrupted run because replayed supersteps
    recompute exactly what the crash discarded — state AND the IOStats
    ledger resume from the snapshot.

    Returns ``(ProgramResult, RecoveryReport)``.
    """
    from .program import run_program

    rep = RecoveryReport()
    plan = plan if plan is not None else FailurePlan({})
    for attempt in range(max_restarts + 1):
        try:
            res = run_program(sg, prog, policy, seeds=seeds,
                              max_supersteps=max_supersteps,
                              checkpoint=checkpoint, resume=(attempt > 0),
                              _plan=plan)
            return res, rep
        except DeviceFailure as e:
            rep.restarts += 1
            step = latest_step(checkpoint.directory)
            rep.resumed_steps.append(step)
            rep.log.append(f"{e}; replaying from "
                           f"{'scratch' if step is None else f'step {step}'}")
    raise DeviceFailure(
        f"gave up after {max_restarts} restarts ({rep.log[-1] if rep.log else ''})"
    )
