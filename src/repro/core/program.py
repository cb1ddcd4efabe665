"""The vertex-program layer: write an algorithm, let the engine run it.

This is the user-facing half of the library (Graphyti's pitch: SEM
performance through an *extensible* vertex-centric interface, not a bag of
six prebuilt algorithms).  The split of responsibilities:

  * a :class:`VertexProgram` says WHAT one superstep means — which vertices
    are in the frontier, what values they multicast, how gathered
    contributions update vertex state, and when the computation has
    converged;
  * :func:`run_program` owns HOW supersteps execute — the single
    ``lax.while_loop`` BSP driver shared by every algorithm.  Per superstep
    it asks the program for its frontier, executes the multicast through
    :func:`repro.core.engine.traverse` (so every program inherits the full
    :class:`~repro.core.engine.ExecutionPolicy` dispatch: push/pull
    direction optimization, multicast/compact/p2p density switching,
    blocked Pallas backends, adaptive work-list bucketing), applies the
    update, accumulates :class:`~repro.core.sem.IOStats`, and tests
    convergence — all on device, no per-step host round-trip.

Every algorithm in :mod:`repro.algs` is an instance of this protocol; a new
algorithm is ~30 lines (see ``examples/custom_program.py`` for
weakly-connected components written purely against the public API).

Protocol
--------
Required hooks (all receive the :class:`~repro.core.sem.SemGraph` so state
can stay minimal)::

    init(sg, seeds) -> state            # build the initial vertex state
    semiring                            # class attr: the gather reduction
    frontier(sg, state) -> Frontier     # who multicasts what this superstep
    apply(sg, state, gathered)          # -> (state', activated)
    converged(sg, state, activated)     # -> bool[] (default: no activations)

Optional hooks with defaults::

    gather(sg, state, fr, policy)       # default: one traverse() call
    activate(sg, state, policy)         # post-apply activation multicast
    prepare_policy(sg, policy)          # pin algorithm-owned policy fields
    max_supersteps(sg)                  # superstep budget (default n + 1)
    finalize(sg, state)                 # state -> ProgramResult.values

``gather`` exists because a few dataflows are more than one logical
multicast per superstep (PR-pull's gather + activation, coreness' skip of
empty removal rounds, fused betweenness' two phases).  Overriding it keeps
such programs on the shared driver — the while loop, IOStats ledger,
convergence, and superstep accounting stay in ONE place.
"""
from __future__ import annotations

import collections
from typing import Any, NamedTuple, Optional
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from .engine import ExecutionPolicy, ResidencyError, traverse
from .sem import IOStats, SemGraph
from .semiring import PLUS_TIMES, Semiring
from .spans import host_read

__all__ = [
    "Frontier",
    "ProgramResult",
    "VertexProgram",
    "run_program",
    "run_program_batched",
    "warn_legacy",
    "legacy_policy",
]

State = Any


def under_trace(*args) -> bool:
    """True inside a JAX transformation: an argument leaf is a tracer, or
    a fresh array is one (``jit`` traces even a closure's constants)."""
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves((args, jnp.zeros(()))))


class Frontier(NamedTuple):
    """One superstep's logical multicast: ``active`` vertices send ``x``.

    ``unexplored`` (optional bool[n]) marks candidate receivers — supplying
    it makes the step a *frontier expansion*, which is what lets a
    ``direction='auto'`` policy run Beamer push<->pull switching (the
    engine streams the candidates' in-edges when that is cheaper).
    """

    x: jnp.ndarray
    active: jnp.ndarray
    unexplored: Optional[jnp.ndarray] = None


class ProgramResult(NamedTuple):
    """Uniform result of every program (and every ``repro.Graph`` method).

    values: the program's answer (``finalize`` of the final state).
    supersteps: BSP iterations executed (int32 scalar).
    iostats: accumulated :class:`~repro.core.sem.IOStats` ledger.
    state: the full final program state, for programs whose answer has
      side products (e.g. betweenness levels, fused-BC shared fetches);
      ``None`` when the values tell the whole story.
    query_supersteps: int32[Q] per-query superstep counts, set only by
      :func:`run_program_batched` — entry q is the superstep at which
      query column q converged (equal to the supersteps of q's solo run),
      or the total superstep count when the budget ran out first.  ``None``
      on unbatched runs.
    """

    values: Any
    supersteps: jnp.ndarray
    iostats: IOStats
    state: Any = None
    query_supersteps: Any = None


class VertexProgram:
    """Base class / protocol for vertex-centric programs (see module doc).

    Subclasses hold only *configuration* (damping factors, thresholds...);
    all per-run data lives in the state pytree returned by ``init``, so one
    program instance can run on any graph, any number of times, inside or
    outside ``jax.jit``.
    """

    #: Semiring of the default ``gather`` (y[dst] = combine(edge_op(x, w))).
    semiring: Semiring = PLUS_TIMES
    #: Policy used when the caller passes none (``None`` -> ExecutionPolicy()).
    default_policy: Optional[ExecutionPolicy] = None
    #: Reverse flow: messages run against the edge direction (BC backward).
    reverse: bool = False
    #: Evaluate ``converged`` on the initial state (with ``activated=None``)
    #: so an already-converged program runs zero supersteps.
    check_initial_convergence: bool = False

    # ---- required hooks -------------------------------------------------
    def init(self, sg: SemGraph, seeds) -> State:
        """Build the initial state pytree (sources, ranks, labels, ...)."""
        raise NotImplementedError

    def frontier(self, sg: SemGraph, state: State) -> Frontier:
        """The superstep's multicast: who is active, what values they send."""
        raise NotImplementedError

    def apply(self, sg: SemGraph, state: State, gathered):
        """Combine gathered contributions into state.

        Returns ``(state', activated)`` where ``activated`` (bool array) is
        the set of vertices whose state changed — the default convergence
        test is "nothing activated".
        """
        raise NotImplementedError

    # ---- optional hooks -------------------------------------------------
    def converged(self, sg: SemGraph, state: State, activated) -> jnp.ndarray:
        """Scalar bool: stop after this superstep.  Default: no activations.

        Programs setting ``check_initial_convergence`` are called once with
        ``activated=None`` before the first superstep and must not rely on
        it.
        """
        return ~jnp.any(activated)

    def gather(self, sg: SemGraph, state: State, fr: Frontier,
               policy: ExecutionPolicy):
        """Execute the frontier's multicast.  Default: one engine traverse.

        Returns ``(gathered, IOStats)``; ``gathered`` may be any pytree —
        ``apply`` is its only consumer.
        """
        return traverse(sg, fr.x, fr.active, self.semiring, policy=policy,
                        unexplored=fr.unexplored, reverse=self.reverse)

    def activate(self, sg: SemGraph, state: State, policy: ExecutionPolicy):
        """Optional post-apply activation multicast (Pregel-style wakeups).

        Returns ``(state', IOStats | None)``.  The default does nothing;
        PR-pull overrides this with its out-edge activation broadcast.
        """
        return state, None

    # ---- batched-query hooks (run_program_batched only) -----------------
    def converged_cols(self, sg: SemGraph, state: State,
                       activated) -> jnp.ndarray:
        """bool[Q]: which query columns have converged this superstep.

        The per-column refinement of ``converged`` used by
        :func:`run_program_batched`.  The default mirrors ``converged``'s
        "nothing activated" test column-wise over an (n, Q) ``activated``
        — correct for any program whose convergence means its frontier
        drained (a converged column then stays converged and contributes
        identity forever, which is what makes early retirement safe).
        """
        return ~jnp.any(activated, axis=0)

    def take_cols(self, state: State, cols, width: int) -> State:
        """Slice query columns ``cols`` out of an (n, ``width``)-batched
        state — how :func:`run_program_batched` retires converged columns
        (compacting the live ones) and captures finished ones.

        The default slices every array leaf whose trailing dimension is
        ``width`` and passes everything else (per-run scalars, O(n)
        vectors) through unchanged.  Programs whose state has a leaf that
        coincidentally ends in a ``width``-sized non-query axis must
        override this.
        """
        cols = jnp.asarray(cols, jnp.int32)

        def leaf(a):
            if getattr(a, "ndim", 0) >= 1 and a.shape[-1] == width:
                return a[..., cols]
            return a

        return jax.tree_util.tree_map(leaf, state)

    def prepare_policy(self, sg: SemGraph,
                       policy: ExecutionPolicy) -> ExecutionPolicy:
        """Pin the policy fields the algorithm owns (e.g. a fixed dataflow
        direction, p2p capacity defaults).  Everything else stays the
        caller's choice."""
        return policy

    def max_supersteps(self, sg: SemGraph) -> int:
        """Superstep budget when the caller does not pass one."""
        return sg.n + 1

    def finalize(self, sg: SemGraph, state: State):
        """Map the final state to ``ProgramResult.values``."""
        return state


def run_program(
    sg: SemGraph,
    prog: VertexProgram,
    policy: Optional[ExecutionPolicy] = None,
    *,
    seeds=None,
    max_supersteps: Optional[int] = None,
    checkpoint=None,
    resume: bool = False,
    _plan=None,
) -> ProgramResult:
    """The one BSP driver behind every algorithm (and ``repro.Graph``).

    One iteration of the ``lax.while_loop`` is one superstep::

        fr                = prog.frontier(sg, state)
        gathered, io_g    = prog.gather(sg, state, fr, policy)   # traverse()
        state, activated  = prog.apply(sg, state, gathered)
        state, io_a       = prog.activate(sg, state, policy)
        done              = prog.converged(sg, state, activated)

    IOStats from every engine call accumulate into one ledger whose
    ``supersteps`` field counts loop iterations; the returned
    ``ProgramResult.supersteps`` equals it.  The loop exits when the
    program reports convergence or the superstep budget is spent.  The
    whole loop stays on device — no host round-trip per superstep, exactly
    like FlashGraph keeping the BSP barrier inside the engine.

    ``policy`` falls back to ``prog.default_policy`` then to a plain
    :class:`ExecutionPolicy`; ``prog.prepare_policy`` then pins the fields
    the algorithm owns.  ``seeds`` is forwarded verbatim to ``prog.init``.

    ``checkpoint=CheckpointSpec(...)`` snapshots the run every ``every_k``
    supersteps (state, frontier, accumulated IOStats, superstep) through
    :mod:`repro.core.recovery`; ``resume=True`` restores the newest
    complete snapshot and continues, *bitwise-equal* to an uninterrupted
    run on every backend and both residencies.  Checkpointed runs execute
    eagerly (segments of the same while-loop body for device residency) —
    they cannot sit under an enclosing ``jax.jit``.  ``_plan`` is the
    supervisor's fault-injection channel (:func:`repro.core.recovery.
    run_supervised`); user code leaves it None.
    """
    if checkpoint is not None or _plan is not None:
        from .recovery import run_program_checkpointed

        return run_program_checkpointed(
            sg, prog, policy, seeds=seeds, max_supersteps=max_supersteps,
            checkpoint=checkpoint, resume=resume, _plan=_plan)
    pol = policy if policy is not None else prog.default_policy
    pol = pol if pol is not None else ExecutionPolicy()
    if pol.residency == "host" or getattr(sg, "is_host_view", False):
        # Host residency runs an eager BSP loop (each superstep plans its
        # host->device streaming batches from the concrete frontier);
        # run_program_host validates the policy/view pairing.
        from .residency import run_program_host

        return run_program_host(sg, prog, pol, seeds=seeds,
                                max_supersteps=max_supersteps)
    if not under_trace(sg, seeds):
        # Eager device runs ride the checkpointed driver with
        # checkpointing off: the SAME while-loop body, traced once and
        # cached across calls (recovery._SEG_CACHE), so repeated runs
        # skip the per-call retrace+recompile this inline path pays.
        # Identical iteration predicate (the budget rides the carry
        # instead of closing over it), bitwise-equal results.
        from .recovery import run_program_checkpointed

        return run_program_checkpointed(
            sg, prog, pol, seeds=seeds, max_supersteps=max_supersteps)
    pol = prog.prepare_policy(sg, pol)
    state0 = prog.init(sg, seeds)
    budget = max_supersteps if max_supersteps is not None \
        else prog.max_supersteps(sg)

    from .recovery import superstep

    def body(carry):
        state, io, it, _ = carry
        state, io, done = superstep(sg, prog, pol, state, io)
        return state, io, it + 1, done

    def cond(carry):
        _, _, it, done = carry
        return jnp.logical_and(~done, it < budget)

    done0 = (
        jnp.asarray(prog.converged(sg, state0, None))
        if prog.check_initial_convergence
        else jnp.zeros((), bool)
    )
    state, io, iters, _ = jax.lax.while_loop(
        cond, body, (state0, IOStats.zero(), jnp.zeros((), jnp.int32), done0)
    )
    return ProgramResult(prog.finalize(sg, state), iters, io, state)


# --------------------------------------------------------------------------
# the batched multi-source driver
# --------------------------------------------------------------------------
_BATCH_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_BATCH_CACHE_SIZE = 8


def _batched_step_fn(sg, prog: VertexProgram, pol: ExecutionPolicy):
    """The device batched superstep, wrapped by
    :func:`repro.core.residency._loopify` so it compiles in the same
    while-loop-body codegen context as the sequential drivers (bitwise
    parity; see ``_loopify``).  Cached across runs like
    ``recovery._SEG_CACHE`` — the cached closure holds ``sg`` strongly, so
    the ``id(sg)`` key cannot be recycled while cached."""
    from .recovery import superstep
    from .residency import _loopify

    def build():
        return _loopify(lambda state, io: superstep(
            sg, prog, pol, state, io, converged=prog.converged_cols))

    try:
        key = (id(sg), type(prog), tuple(sorted(prog.__dict__.items())), pol)
    except TypeError:  # unhashable program config: run uncached
        return build()
    hit = _BATCH_CACHE.get(key)
    if hit is None:
        hit = _BATCH_CACHE[key] = build()
        while len(_BATCH_CACHE) > _BATCH_CACHE_SIZE:
            _BATCH_CACHE.popitem(last=False)
    else:
        _BATCH_CACHE.move_to_end(key)
    return hit


def _pow2_at_least(k: int) -> int:
    g = 1
    while g < max(1, k):
        g *= 2
    return g


def _reassemble_values(parts, Q: int):
    """Stitch per-part finalized values (each with a trailing column axis)
    back into original column order.  ``parts`` is a list of
    ``(orig_cols, values)``; leaves whose trailing dim is not the part's
    column count (per-run scalars) take the last part's value."""
    order = np.concatenate([np.asarray(c, np.int64) for c, _ in parts])
    perm = jnp.asarray(np.argsort(order), jnp.int32)
    widths = [len(c) for c, _ in parts]

    def cat(*leaves):
        if all(getattr(a, "ndim", 0) >= 1 and a.shape[-1] == w
               for a, w in zip(leaves, widths)):
            return jnp.concatenate(leaves, axis=-1)[..., perm]
        return leaves[-1]

    return jax.tree_util.tree_map(cat, *(v for _, v in parts))


def run_program_batched(
    sg: SemGraph,
    prog: VertexProgram,
    policy: Optional[ExecutionPolicy] = None,
    *,
    seeds=None,
    max_supersteps: Optional[int] = None,
    checkpoint=None,
    resume: bool = False,
    _plan=None,
) -> ProgramResult:
    """The Q-query BSP driver: one superstep loop serving Q concurrent
    query columns, each streamed edge tile amortized across all of them.

    Runs a program whose state/frontier carry a trailing query axis
    (``frontier().active`` must be (n, Q)) through the same superstep body
    as :func:`run_program`, with three additions:

      * **per-query convergence** — ``prog.converged_cols`` yields a
        bool[Q] mask per superstep; ``ProgramResult.query_supersteps[q]``
        records the superstep at which column q converged, which equals
        the supersteps of q's solo run (a batched column's frontier
        evolves exactly as its solo frontier — the union fetch only adds
        identity contributions from other lanes).
      * **early retirement** — converged columns are retired by compacting
        the live columns into pow2 Q-buckets (``prog.take_cols``), so the
        per-superstep state cost tracks the LIVE query count and the step
        function is traced at most ``log2(Q) + 1`` times, never per
        retirement.  Retired columns' values are captured at retirement
        and stitched back into original column order at exit.  With
        ``checkpoint=`` set, retirement is disabled (snapshots need a
        fixed schema) — the run stays at width Q and converged columns
        ride along inactive, which costs state memory but no extra I/O
        (an empty frontier adds nothing to the union).
      * **amortization accounting** — ``IOStats.queries`` is stamped to Q
        at exit, so ``iostats.host_bytes / queries`` (etc.) is the
        measured per-query cost the batching exists to shrink.

    The loop is eager (retirement decisions need concrete convergence
    masks); like the host driver it cannot sit under ``jax.jit``.  Both
    residencies are supported — under ``residency='host'`` the streamed
    work-list is the column-union of live frontiers, which is where the
    host-link amortization is realized.

    ``ProgramResult.state`` is the final full-width state when no column
    was retired mid-run, ``None`` otherwise (values are reassembled from
    per-part ``finalize`` calls).
    """
    if under_trace(sg, seeds):
        raise ValueError(
            "run_program_batched cannot run under jit: column "
            "retirement and per-query bookkeeping need concrete "
            "convergence masks each superstep"
        )
    pol = policy if policy is not None else prog.default_policy
    pol = pol if pol is not None else ExecutionPolicy()
    is_host = pol.residency == "host" or getattr(sg, "is_host_view", False)
    if is_host:
        if not getattr(sg, "is_host_view", False):
            raise ResidencyError(
                "residency='host' policy met a device-resident graph; run "
                "through repro.Graph or build a host view with "
                "repro.core.residency.host_graph()"
            )
        if pol.residency != "host":
            raise ResidencyError(
                "device-residency policy met a host-resident graph view; "
                "use ExecutionPolicy(residency='host') or build a device "
                "view with device_graph()"
            )
    pol = prog.prepare_policy(sg, pol)
    state = prog.init(sg, seeds)
    fr0 = prog.frontier(sg, state)
    if fr0.active.ndim != 2:
        raise ValueError(
            "run_program_batched needs an (n, Q)-batched program: "
            f"frontier().active has shape {fr0.active.shape}"
        )
    Q = int(fr0.active.shape[-1])
    budget = int(max_supersteps if max_supersteps is not None
                 else prog.max_supersteps(sg))

    ctx = None
    if checkpoint is not None:
        from .recovery import _CheckpointCtx, run_fingerprint

        ctx = _CheckpointCtx(checkpoint,
                             run_fingerprint(sg, prog, pol, seeds))
    from .recovery import maybe_fail

    def _wrap(state, done_at):
        return {"done_at": jnp.asarray(done_at, jnp.int32), "state": state}

    if is_host:
        frontier_fn, apply_fn = sg._hooks(prog, pol)

        def step(state, io):
            fr = frontier_fn(state)
            gathered, st = prog.gather(sg, state, fr, pol)
            state, activated = apply_fn(state, gathered)
            state, st_act = prog.activate(sg, state, pol)
            io = io + st
            if st_act is not None:
                io = io + st_act
            io = io._replace(supersteps=io.supersteps + 1)
            conv = prog.converged_cols(sg, state, activated)
            return state, io, conv

        def union_active(state):
            a = frontier_fn(state).active
            return jnp.any(a, axis=-1) if a.ndim > 1 else a
    else:
        step = _batched_step_fn(sg, prog, pol)

        def union_active(state):
            a = prog.frontier(sg, state).active
            return jnp.any(a, axis=-1) if a.ndim > 1 else a

    done_at = np.full(Q, -1, np.int64)
    io = IOStats.zero()
    it = 0
    done = (bool(host_read(prog.converged(sg, state, None)))
            if prog.check_initial_convergence else False)
    if done:
        done_at[:] = 0
    if resume and ctx is not None:
        hit = ctx.try_restore(sg, _wrap(state, done_at))
        if hit is not None:
            wrapped, io, it, finished = hit
            state = wrapped["state"]
            done_at = np.asarray(wrapped["done_at"], np.int64)
            if finished:
                return ProgramResult(
                    prog.finalize(sg, state), jnp.asarray(it, jnp.int32),
                    io._replace(queries=jnp.asarray(Q, jnp.int32)), state,
                    jnp.asarray(done_at, jnp.int32))
            done = False  # an unfinished snapshot is mid-loop by definition

    retire = ctx is None  # snapshots need a fixed (n, Q) schema
    cur = list(range(Q))  # original column at each live position
    width = Q  # current (pow2-padded) column count of `state`
    parts = []  # (orig cols, finalized values) captured at retirement

    try:
        while not done and it < budget:
            maybe_fail(_plan, it)
            with jax.profiler.TraceAnnotation("graphyti.superstep", it=it):
                state, io, conv = step(state, io)
                conv_np = host_read(conv)
            it += 1
            for i, q in enumerate(cur):
                if conv_np[i] and done_at[q] < 0:
                    done_at[q] = it
            live = [i for i, q in enumerate(cur) if done_at[q] < 0]
            done = not live
            if retire and not done:
                g = _pow2_at_least(len(live))
                if g < width:
                    dropped = [i for i, q in enumerate(cur)
                               if done_at[q] >= 0]
                    parts.append((
                        [cur[i] for i in dropped],
                        prog.finalize(
                            sg, prog.take_cols(state, dropped, width)),
                    ))
                    # Pad to the pow2 bucket with a converged column: it is
                    # inactive forever, so it adds no frontier mass and no
                    # fetches — only slots.
                    cols = live + [dropped[0]] * (g - len(live))
                    state = prog.take_cols(state, cols, width)
                    cur = [cur[i] for i in live]
                    width = g
            finished = done or it >= budget
            if finished:
                done_at[done_at < 0] = it  # budget-exhausted columns
            if ctx is not None and ctx.due(it, finished):
                ctx.save(it, finished, _wrap(state, done_at), io,
                         union_active(state))
    except BaseException:
        if ctx is not None:
            ctx.wait()  # drain any in-flight async save before unwinding
        raise
    done_at[done_at < 0] = it  # zero-superstep exits
    if ctx is not None:
        if it == 0:
            ctx.save(0, True, _wrap(state, done_at), io,
                     jnp.zeros(sg.n, bool))
        ctx.wait()

    io = io._replace(queries=jnp.asarray(Q, jnp.int32))
    if parts:
        parts.append((cur, prog.finalize(
            sg, prog.take_cols(state, list(range(len(cur))), width))))
        values = _reassemble_values(parts, Q)
        final_state = None
    else:
        values = prog.finalize(sg, state)
        final_state = state
    return ProgramResult(values, jnp.asarray(it, jnp.int32), io, final_state,
                         jnp.asarray(done_at, jnp.int32))


# --------------------------------------------------------------------------
# the ONE deprecation path for every legacy entry point
# --------------------------------------------------------------------------
def warn_legacy(entry: str, replacement: str, *, kwargs: Optional[dict] = None,
                stacklevel: int = 3) -> None:
    """Emit the library's single consistent :class:`DeprecationWarning`.

    Every pre-façade entry point (``bfs_multi``, ``pagerank_push/pull``,
    ``bc_*``, ``coreness``, ``diameter_*``) and every per-algorithm engine
    kwarg (``backend=``, ``chunk_cap=``, ...) funnels through here, so the
    message shape — and the filter key users silence — is uniform.

    ``kwargs``: the deprecated keyword arguments the caller *actually
    passed* (non-``None`` values); they are named in the message with their
    :class:`~repro.core.engine.ExecutionPolicy` replacement.

    ``stacklevel`` must land the warning on the *user's* call site (the
    default fits a shim calling this directly; :func:`legacy_policy` adds
    a frame) — mis-attributed DeprecationWarnings are filtered out by
    Python's default ``__main__``-only filter and unreachable by
    module-targeted filterwarnings.
    """
    dead = sorted(k for k, v in (kwargs or {}).items() if v is not None)
    msg = f"{entry} is deprecated; use {replacement}"
    if dead:
        msg += (
            f" (deprecated kwarg{'s' if len(dead) > 1 else ''} "
            f"{', '.join(dead)}: set the ExecutionPolicy field instead)"
        )
    warnings.warn(msg, DeprecationWarning, stacklevel=stacklevel)


def legacy_policy(
    entry: str,
    replacement: str,
    policy: Optional[ExecutionPolicy],
    default: Optional[ExecutionPolicy],
    **deprecated,
) -> ExecutionPolicy:
    """Deprecation-warn + merge a legacy call's kwargs into a policy.

    The merge is :func:`repro.core.engine.as_policy` (explicit ``policy``
    wins as the base, any non-``None`` deprecated kwarg overrides its
    field); the warning is :func:`warn_legacy` — one path for all shims.
    """
    from .engine import as_policy

    warn_legacy(entry, replacement, kwargs=deprecated, stacklevel=4)
    return as_policy(policy, default, **deprecated)
