"""``analyze()`` and the six SEM contract rules (R1–R6).

The analyzer traces a :class:`~repro.core.VertexProgram` the way the
driver will run it and walks the resulting jaxprs against a rule
registry.  For device-resident views it traces the *loopified superstep
body* — :func:`repro.core.recovery.superstep_body`, the very function
``recovery._build_segment_fn`` wraps in the driver's ``lax.while_loop``
— so the analyzed jaxpr is exactly the loop that runs (mode ``'body'``).
Under ``residency='host'`` the streaming executor is eager Python with no
whole-body jaxpr; the analyzer then traces the per-hook jaxprs the host
driver itself jits (``frontier``/``apply``/``converged``; mode
``'hooks'``), and reports what it had to skip.

Rules (stable IDs; severities in :data:`repro.analysis.report.RULES`):

R1 residency
    Under ``residency='host'`` no eqn in a user hook may materialize an
    O(m)-shaped aval on device (a dimension equal to ``sg.m``) — the
    accidental full-edge gather that silently un-does semi-external
    memory.  Engine-owned eqns (``repro/core``, ``repro/kernels``) are
    exempt: under host residency the engine streams its O(m) work.
    Runtime counterpart: :class:`repro.core.ResidencyError`.
R2 host-sync
    Concretization points (``int()``/``bool()``/``np.asarray`` on a
    tracer) and host callbacks (``pure_callback``/``io_callback``/
    ``debug_callback``) inside the traced BSP body.  What would be a
    mid-run crash or a per-superstep host round-trip becomes a
    pre-flight diagnostic naming the offending hook and line.
R3 retrace audit
    Carry avals that drift across supersteps — weak-type flips
    (warning: the segment driver canonicalizes, at the cost of the PR 7
    retrace bug class) or dtype/shape drift (error: the while_loop
    cannot typecheck) — plus non-hashable program/policy configs that
    silently defeat ``recovery._SEG_CACHE``/``program._BATCH_CACHE``.
R4 IOStats order-invariance
    Only ``x_fetches`` (schedule-sensitive) and ``host_bytes``
    (residency-sensitive) may depend on tile/batch order.  The analyzer
    *taints* those two fields at every IOStats construction during a
    trace of the gather/apply/activate chain and propagates value
    dependence through the jaxpr (:func:`repro.analysis.inspect.
    taint_jaxpr`): any other IOStats field — or any program-state leaf —
    reached by the taint breaks the order-invariance ledger contract.
R5 semiring lawfulness
    Custom :class:`~repro.core.semiring.Semiring` s must have a lawful
    identity (``combine(identity, v) == v``), an identity-absorbing
    ``edge_op`` (``edge_op(identity, w) == identity`` — padding lanes
    must vanish), and a dtype-stable ``edge_op`` at the frontier dtype.
R6 convergence guard
    ``converged()`` must read carried state (or the superstep's
    activations): a trivially-constant predicate either exits at
    superstep 0 or spins until the budget.
"""
from __future__ import annotations

import contextlib
import inspect as _src
from collections import OrderedDict
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import Literal
import numpy as np

from ..core.engine import ExecutionPolicy
from ..core.program import VertexProgram
from ..core.recovery import superstep_body
from ..core.sem import IOStats
from .inspect import (
    eqn_location,
    frame_is_engine,
    iter_eqns,
    location_from_exception,
    taint_jaxpr,
    user_location,
)
from .report import RULES, AnalysisReport, Finding

__all__ = ["analyze"]

_HOOKS = ("init", "frontier", "gather", "apply", "activate", "converged",
          "finalize")
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback")
_TRACER_ERRORS = tuple(
    e for e in (
        getattr(jax.errors, "ConcretizationTypeError", None),
        getattr(jax.errors, "TracerArrayConversionError", None),
        getattr(jax.errors, "TracerBoolConversionError", None),
        getattr(jax.errors, "TracerIntegerConversionError", None),
    ) if e is not None
)


def _finding(rule: str, message: str, location: str = "",
             hook: Optional[str] = None,
             severity: Optional[str] = None) -> Finding:
    return Finding(rule, severity or RULES[rule][0], message, location, hook)


def _def_site(prog, hook: Optional[str] = None) -> str:
    """``file:line`` of a hook override (or the program class) — the
    location rules use when a violation is a property of the hook, not
    of one eqn."""
    try:
        obj = getattr(type(prog), hook) if hook else type(prog)
        obj = _src.unwrap(obj)
        file = _src.getsourcefile(obj)
        _, line = _src.getsourcelines(obj)
        return f"{file}:{line}"
    except (OSError, TypeError):
        return ""


def _overridden(prog, hook: str) -> bool:
    return getattr(type(prog), hook, None) is not \
        getattr(VertexProgram, hook, None)


def _hook_from_tb(exc: BaseException) -> Optional[str]:
    hit, tb = None, exc.__traceback__
    while tb is not None:
        if tb.tb_frame.f_code.co_name in _HOOKS:
            hit = tb.tb_frame.f_code.co_name
        tb = tb.tb_next
    return hit


class _TraceFail(Exception):
    """Internal: a traced step failed; dependent rules are skipped."""


def _run_traced(findings: list, notes: list, what: str, fn, *,
                soft: bool = False):
    """Run a tracing step.  Tracer/concretization errors become an R2
    finding (named hook, offending line) + :class:`_TraceFail`; with
    ``soft=True`` any other exception becomes a coverage note instead of
    propagating (used where the analyzer substituted a guessed aval and
    a failure may be its own guess's fault, not the program's)."""
    try:
        return fn()
    except _TRACER_ERRORS as e:
        hook = _hook_from_tb(e) or what
        first = str(e).splitlines()[0] if str(e) else type(e).__name__
        findings.append(_finding(
            "R2", f"host synchronization while tracing {what}: {first}",
            location_from_exception(e), hook))
        raise _TraceFail from e
    except Exception as e:  # noqa: BLE001
        if soft:
            notes.append(f"{what} not analyzed: {type(e).__name__}: {e}")
            raise _TraceFail from e
        raise


# --------------------------------------------------------------------------
# individual rules
# --------------------------------------------------------------------------
def _rule_r1_residency(jaxprs, n: int, m: int, notes: list) -> List[Finding]:
    if m <= 1 or m == n:
        notes.append("R1 skipped: m and n are indistinguishable on this "
                     f"graph (n={n}, m={m})")
        return []
    out = []
    for hook, closed in jaxprs:
        jx = getattr(closed, "jaxpr", closed)
        for cv in jx.constvars:
            shape = getattr(cv.aval, "shape", ())
            if any(int(d) == m for d in shape):
                out.append(_finding(
                    "R1", f"hook closes over an O(m) constant "
                          f"({cv.aval.str_short()}) that would be shipped "
                          "to device under residency='host'",
                    _def_site_cache.get(hook, ""), hook))
        for eqn in iter_eqns(closed):
            loc = user_location(eqn)
            if loc is None or frame_is_engine(loc[0]):
                continue
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if any(int(d) == m for d in shape):
                    out.append(_finding(
                        "R1", f"O(m)-shaped aval {v.aval.str_short()} "
                              f"materialized on device by "
                              f"'{eqn.primitive.name}' under "
                              "residency='host' (m="
                              f"{m}; edge-sized data must stream)",
                        f"{loc[0]}:{loc[1]}", hook))
    return out


_def_site_cache: dict = {}  # hook -> def-site location for the current run


def _rule_r2_callbacks(jaxprs) -> List[Finding]:
    out = []
    for hook, closed in jaxprs:
        for eqn in iter_eqns(closed):
            if eqn.primitive.name in _CALLBACK_PRIMS:
                out.append(_finding(
                    "R2", f"host callback '{eqn.primitive.name}' inside "
                          "the traced BSP body: every superstep pays a "
                          "device->host->device round trip",
                    eqn_location(eqn), hook))
    return out


def _rule_r3_hashability(prog, pol) -> List[Finding]:
    out = []
    for k in sorted(prog.__dict__):
        try:
            hash((k, prog.__dict__[k]))
        except TypeError:
            out.append(_finding(
                "R3", f"program config attribute {k!r} "
                      f"({type(prog.__dict__[k]).__name__}) is not "
                      "hashable: every run misses _SEG_CACHE/_BATCH_CACHE "
                      "and re-traces the loop",
                _def_site(prog), None))
    try:
        hash(pol)
    except TypeError:
        out.append(_finding(
            "R3", "policy is not hashable (a mutable value reached a "
                  "policy field): trace caches are defeated",
            _def_site(prog), None))
    return out


def _leaf_sig(sds) -> Tuple:
    return (tuple(sds.shape), jnp.result_type(sds.dtype),
            bool(getattr(sds, "weak_type", False)))


def _rule_r3_drift(in_tree, out_tree, hook: str, where: str,
                   what: str) -> List[Finding]:
    flat_in = jax.tree_util.tree_flatten_with_path(in_tree)[0]
    flat_out, tdef_out = jax.tree_util.tree_flatten_with_path(out_tree)
    tdef_in = jax.tree_util.tree_structure(in_tree)
    if tdef_in != tdef_out:
        return [_finding(
            "R3", f"{what} tree structure changes across supersteps "
                  f"({tdef_in} -> {tdef_out}): the BSP while_loop cannot "
                  "carry it", where, hook, severity="error")]
    out = []
    for (path, a), (_, b) in zip(flat_in, flat_out):
        sa, sb = _leaf_sig(a), _leaf_sig(b)
        if sa == sb:
            continue
        name = jax.tree_util.keystr(path)
        if sa[:2] != sb[:2]:
            out.append(_finding(
                "R3", f"{what} leaf {name} drifts across supersteps: "
                      f"{a.dtype}{list(a.shape)} -> {b.dtype}"
                      f"{list(b.shape)} — the while_loop carry cannot "
                      "typecheck", where, hook, severity="error"))
        else:
            out.append(_finding(
                "R3", f"{what} leaf {name} flips weak_type "
                      f"({sa[2]} -> {sb[2]}) across supersteps: every "
                      "segment boundary re-traces (the PR 7 recompile "
                      "storm; make init produce strongly-typed leaves)",
                where, hook, severity="warning"))
    return out


def _rule_r5_semiring(prog, sg, x_dtype) -> List[Finding]:
    sr = getattr(prog, "semiring", None)
    if sr is None:
        return []
    loc = _def_site(prog)
    if sr.combine not in ("add", "min", "max"):
        return [_finding("R5", f"unknown combine {sr.combine!r}: the "
                               "engine's scatter paths implement "
                               "add/min/max", loc)]
    d = jnp.result_type(x_dtype if x_dtype is not None else sr.identity)
    ident = jnp.asarray(sr.identity, d)
    out = []
    if d == jnp.bool_:
        probes = [False, True]
    else:
        probes = [0, 1, 2] if jnp.issubdtype(d, jnp.integer) \
            else [-3.5, -1.0, 0.0, 1.0, 2.75]
    # identity law: combine(identity, v) == v
    for v in probes:
        vv = jnp.asarray(v, d)
        got = sr.combine_elem(ident, vv)
        if not bool(got == vv):
            out.append(_finding(
                "R5", f"identity {sr.identity!r} is not neutral for "
                      f"combine={sr.combine!r} at {d}: "
                      f"combine(identity, {v!r}) == {got} != {v!r} — "
                      "skipped chunks and padding lanes would corrupt "
                      "results", loc))
            break
    # absorption: edge_op(identity, w) == identity (padding lanes vanish)
    weighted = bool(getattr(sg, "weighted", False))
    for w in ([jnp.asarray(2.0, jnp.float32)] if weighted else [None]):
        try:
            got = sr.edge_op(ident, w)
        except TypeError:
            continue
        if not bool(got == ident):
            out.append(_finding(
                "R5", f"edge_op does not absorb the identity: "
                      f"edge_op({sr.identity!r}, {w}) == {got} — inactive "
                      "lanes would contribute non-identity terms", loc))
            break
    # dtype stability of edge_op at the frontier dtype
    if x_dtype is not None:
        w_sds = jax.ShapeDtypeStruct((), jnp.float32) if weighted else None
        try:
            y = jax.eval_shape(sr.edge_op, jax.ShapeDtypeStruct((), d),
                               w_sds)
            if jnp.result_type(y.dtype) != d:
                out.append(_finding(
                    "R5", f"edge_op changes dtype: {d} -> {y.dtype} — "
                          "the scatter accumulator is allocated at the "
                          "frontier dtype", loc))
        except Exception:  # noqa: BLE001 - edge_op may reject abstract w
            pass
    return out


def _rule_r6_converged(closed, hook_loc: str) -> List[Finding]:
    jx = closed.jaxpr
    flat_out = jx.outvars
    if all(isinstance(v, Literal) for v in flat_out):
        val = flat_out[0].val if flat_out else None
        return [_finding(
            "R6", f"converged() is the constant {val!r}: the loop "
            + ("exits at superstep 0" if np.all(val) else
               "can only stop at the superstep budget"),
            hook_loc, "converged")]
    taint = taint_jaxpr(closed, [True] * len(jx.invars))
    if flat_out and not any(taint):
        return [_finding(
            "R6", "converged() does not read carried state or the "
                  "superstep's activations (its value is derived from "
                  "constants): the loop exit is decided before the run "
                  "starts", hook_loc, "converged")]
    return []


# --------------------------------------------------------------------------
# R4: taint x_fetches/host_bytes at IOStats construction, track the flow
# --------------------------------------------------------------------------
@contextlib.contextmanager
def _tainted_iostats(tx, th):
    """While active, every IOStats constructed carries ``tx`` in
    ``x_fetches`` and ``th`` in ``host_bytes``.  All construction paths
    (``zero()``, ``__add__``, engine ``IOStats(...)`` sites) funnel
    through ``__new__``, so the taint marks the schedule-sensitive slots
    at their source."""
    orig = IOStats.__new__

    def tainted_new(cls, requests, records, chunks_skipped, messages,
                    supersteps, bytes_moved, x_fetches, host_bytes,
                    retries=0, queries=0):
        return orig(cls, requests, records, chunks_skipped, messages,
                    supersteps, bytes_moved, x_fetches + tx,
                    host_bytes + th, retries, queries)

    IOStats.__new__ = tainted_new
    try:
        yield
    finally:
        IOStats.__new__ = orig


def _rule_r4_iostats(prog, sg, pol, state0) -> List[Finding]:
    def fn(tx, th, s):
        with _tainted_iostats(tx, th):
            fr = prog.frontier(sg, s)
            g, st = prog.gather(sg, s, fr, pol)
            s2, _activated = prog.apply(sg, s, g)
            s3, st2 = prog.activate(sg, s2, pol)
            io = st if st2 is None else st + st2
        return s3, io

    z = jnp.zeros((), jnp.int32)
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(z, z, state0)
    n_in = len(closed.jaxpr.invars)
    out_taint = taint_jaxpr(closed, [True, True] + [False] * (n_in - 2))

    # flatten((state, io)) order: state leaves first, then the 10 IOStats
    # fields — name and allow-list each output slot accordingly.
    s3_sds, _io_sds = out_shape
    s3_paths, _ = jax.tree_util.tree_flatten_with_path(s3_sds)
    names = [f"state{jax.tree_util.keystr(p)}" for p, _ in s3_paths] \
        + [f"IOStats.{f}" for f in IOStats._fields]
    allowed = [False] * len(s3_paths) \
        + [f in ("x_fetches", "host_bytes") for f in IOStats._fields]
    assert len(allowed) == len(out_taint), (len(allowed), len(out_taint))

    hook = "gather" if _overridden(prog, "gather") else (
        "activate" if _overridden(prog, "activate") else None)
    where = _def_site(prog, hook) if hook else _def_site(prog)
    out = []
    for name, tainted, ok in zip(names, out_taint, allowed):
        if tainted and not ok:
            kind = "order-invariant IOStats field" \
                if name.startswith("IOStats") else "program state leaf"
            out.append(_finding(
                "R4", f"{kind} {name} depends on the schedule-sensitive "
                      "counters (x_fetches/host_bytes): its value would "
                      "change with tile/batch order, breaking the "
                      "order-invariant ledger contract", where, hook))
    return out


# --------------------------------------------------------------------------
# analyze()
# --------------------------------------------------------------------------
_ANALYSIS_CACHE: "OrderedDict[Any, Tuple[Any, AnalysisReport]]" = \
    OrderedDict()
_ANALYSIS_CACHE_SIZE = 32


def _seeds_key(seeds):
    if seeds is None:
        return None
    try:
        hash(seeds)
        return seeds
    except TypeError:
        pass
    try:
        leaves = jax.tree_util.tree_leaves(seeds)
        return tuple((np.asarray(l).shape, str(np.asarray(l).dtype),
                      np.asarray(l).tobytes()) for l in leaves)
    except Exception:  # noqa: BLE001 - uncacheable seeds: analyze fresh
        return object()


def _resolve_view(graph, prog, pol):
    if callable(getattr(graph, "_sem", None)) \
            and hasattr(graph, "host_view"):
        return graph._sem(pol, prog)
    return graph


def analyze(program, graph, policy: Optional[ExecutionPolicy] = None, *,
            seeds=None) -> AnalysisReport:
    """Statically check ``program`` against the SEM contracts it would
    run under on ``graph`` with ``policy``.

    ``graph`` may be a :class:`repro.Graph` session (the policy-matched
    cached view is resolved exactly as ``Graph.run`` would), a device
    :class:`~repro.core.SemGraph`, or a host
    :class:`~repro.core.residency.HostGraph`.  ``seeds`` is forwarded to
    ``program.init`` (source vertices, reset distributions, ...).
    Results are cached per ``(view, program config, policy, seeds)`` —
    ``Graph.run(analyze=True)`` in a loop pays the analysis once.
    """
    prog = program() if isinstance(program, type) else program
    pol = policy if policy is not None else prog.default_policy
    pol = pol if pol is not None else ExecutionPolicy()
    sg = _resolve_view(graph, prog, pol)
    try:
        key = (id(sg), type(prog), tuple(sorted(prog.__dict__.items())),
               pol, _seeds_key(seeds))
        hit = _ANALYSIS_CACHE.get(key)
    except TypeError:
        key = hit = None
    if hit is not None:
        _ANALYSIS_CACHE.move_to_end(key)
        return hit[1]
    report = _analyze_uncached(prog, sg, pol, seeds)
    if key is not None:
        _ANALYSIS_CACHE[key] = (sg, report)  # sg ref pins id(sg) live
        while len(_ANALYSIS_CACHE) > _ANALYSIS_CACHE_SIZE:
            _ANALYSIS_CACHE.popitem(last=False)
    return report


def _analyze_uncached(prog, sg, pol, seeds) -> AnalysisReport:
    findings: List[Finding] = []
    notes: List[str] = []
    is_host = bool(getattr(sg, "is_host_view", False)) \
        or pol.residency == "host"
    mode = "hooks" if is_host else "body"
    polname = (f"ExecutionPolicy(backend={pol.backend!r}, "
               f"direction={pol.direction!r}, residency={pol.residency!r})")

    pol = prog.prepare_policy(sg, pol)
    findings += _rule_r3_hashability(prog, pol)
    state0 = prog.init(sg, seeds)
    n, m = int(sg.n), int(sg.m)
    _def_site_cache.clear()
    for h in _HOOKS:
        _def_site_cache[h] = _def_site(prog, h)

    jaxprs: List[Tuple[str, Any]] = []  # (hook, ClosedJaxpr) for R1/R2
    fr_sds = act_sds = None

    if mode == "body":
        body = superstep_body(sg, prog, pol)
        try:
            budget = int(prog.max_supersteps(sg))
        except Exception:  # noqa: BLE001
            budget = n + 1
        carry0 = (state0, IOStats.zero(), jnp.asarray(0, jnp.int32),
                  jnp.zeros((), bool), jnp.asarray(budget, jnp.int32))
        try:
            closed, out_sds = _run_traced(
                findings, notes, "the BSP superstep body",
                lambda: jax.make_jaxpr(body, return_shape=True)(carry0))
            jaxprs.append(("superstep", closed))
            in_sds = jax.eval_shape(lambda c: c, carry0)
            findings += _rule_r3_drift(in_sds[0], out_sds[0], "apply",
                                       _def_site_cache["apply"],
                                       "state carry")
            findings += _rule_r3_drift(in_sds[1], out_sds[1], "gather",
                                       _def_site_cache["gather"],
                                       "IOStats carry")
            fr_sds = jax.eval_shape(lambda s: prog.frontier(sg, s), state0)
            act_sds = jax.eval_shape(
                lambda s: prog.apply(
                    sg, s, prog.gather(sg, s, prog.frontier(sg, s),
                                       pol)[0])[1], state0)
            try:
                findings += _run_traced(
                    findings, notes, "the IOStats flow (rule R4)",
                    lambda: _rule_r4_iostats(prog, sg, pol, state0))
            except _TraceFail:
                notes.append("rule R4 skipped: the IOStats taint trace "
                             "did not complete")
        except _TraceFail:
            notes.append("rules R3 (drift), R4, R6 skipped: the superstep "
                         "body did not trace")
    else:
        # residency='host': the streaming executor is eager; analyze the
        # hooks the host driver jits (frontier/apply/converged) and say
        # what stays out of scope.
        notes.append("mode=hooks (residency='host'): gather/activate run "
                     "in the eager streaming executor; R4 is covered by "
                     "the runtime order-invariance parity gates")
        try:
            fr_closed, fr_sds = _run_traced(
                findings, notes, "the frontier hook",
                lambda: jax.make_jaxpr(
                    lambda s: prog.frontier(sg, s),
                    return_shape=True)(state0))
            jaxprs.append(("frontier", fr_closed))
        except _TraceFail:
            fr_sds = None
        if fr_sds is not None:
            g_sds = jax.ShapeDtypeStruct(fr_sds.x.shape, fr_sds.x.dtype)
            soft = _overridden(prog, "gather")
            if soft:
                notes.append("gather override is eager under "
                             "residency='host'; apply analyzed against "
                             "the default gathered aval")
            try:
                ap_closed, ap_sds = _run_traced(
                    findings, notes, "the apply hook",
                    lambda: jax.make_jaxpr(
                        lambda s, g: prog.apply(sg, s, g),
                        return_shape=True)(state0, g_sds), soft=soft)
                jaxprs.append(("apply", ap_closed))
                st_sds, act_sds = ap_sds
                in_sds = jax.eval_shape(lambda s: s, state0)
                findings += _rule_r3_drift(
                    in_sds, st_sds, "apply", _def_site_cache["apply"],
                    "state carry")
            except _TraceFail:
                pass
        if _overridden(prog, "activate"):
            notes.append("activate override is eager under "
                         "residency='host'; not traced")

    # R6 + converged-hook jaxpr (both modes)
    if act_sds is not None:
        try:
            conv_closed = _run_traced(
                findings, notes, "the converged hook",
                lambda: jax.make_jaxpr(
                    lambda s, a: prog.converged(sg, s, a))(state0, act_sds))
            jaxprs.append(("converged", conv_closed))
            findings += _rule_r6_converged(conv_closed,
                                           _def_site_cache["converged"])
        except _TraceFail:
            pass
    else:
        notes.append("rule R6 skipped: no activation aval to trace "
                     "converged() against")

    x_dtype = fr_sds.x.dtype if fr_sds is not None else None
    findings += _rule_r5_semiring(prog, sg, x_dtype)
    findings += _rule_r2_callbacks(jaxprs)
    if pol.residency == "host":
        findings += _rule_r1_residency(jaxprs, n, m, notes)

    seen, uniq = set(), []
    for f in sorted(findings, key=lambda f: (f.rule, f.location, f.message)):
        k = (f.rule, f.location, f.message)
        if k not in seen:
            seen.add(k)
            uniq.append(f)
    return AnalysisReport(program=type(prog).__name__, policy=polname,
                          mode=mode, findings=tuple(uniq),
                          notes=tuple(notes))
