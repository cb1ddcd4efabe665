"""Jaxpr-walking machinery: eqn iteration, source attribution, dataflow.

Three capabilities the rules in :mod:`repro.analysis.rules` share:

* :func:`iter_eqns` — depth-first iteration over every eqn of a (closed)
  jaxpr *including* the sub-jaxprs carried in eqn params (``pjit`` call
  bodies, ``cond`` branches, ``while``/``scan`` bodies, custom-derivative
  wrappers), so a rule that scans for a primitive or an aval shape sees
  the whole program, not just the top level.

* :func:`eqn_location` — the innermost *user* stack frame of an eqn's
  ``source_info``, as a clickable ``file:line`` string.  JAX already
  excludes its own frames from ``user_frames``; we additionally classify
  frames inside the engine (``repro/core``, ``repro/kernels``) so rules
  can tell "the user's hook materialized this" from "the engine's own
  dispatch did" (:func:`frame_is_engine`).

* :func:`taint_jaxpr` — forward value-dependence ("taint") propagation:
  given which jaxpr inputs are tainted, which outputs transitively depend
  on them?  Structured control flow is analyzed *precisely* — per-branch
  for ``cond``, to a fixpoint over the carry for ``while``/``scan`` —
  because the engine's own dispatch is a tower of ``lax.cond`` s and an
  any-in/all-out approximation would smear taint across every IOStats
  field and drown rule R4 in false positives.  Unknown primitives with
  sub-jaxprs fall back to that conservative smear (sound, never silently
  under-taints).
"""
from __future__ import annotations

import sysconfig
from typing import Iterator, List, Optional, Sequence, Tuple

from jax.extend import core as _core

__all__ = [
    "eqn_location",
    "frame_is_engine",
    "iter_eqns",
    "location_from_exception",
    "taint_jaxpr",
    "user_location",
]

# Source files owned by the engine/kernels: eqns whose innermost user
# frame lands here are library code, exempt from user-hook rules (R1).
_ENGINE_PARTS = ("repro/core/", "repro/kernels/", "repro\\core\\",
                 "repro\\kernels\\")
_NOISE_PARTS = ("repro/analysis/", "repro\\analysis\\", "/jax/", "\\jax\\",
                "jax/_src", "site-packages", sysconfig.get_paths()["stdlib"])


def frame_is_engine(file_name: str) -> bool:
    return any(p in file_name for p in _ENGINE_PARTS)


def user_location(eqn) -> Optional[Tuple[str, int, str]]:
    """``(file, line, function)`` of the eqn's innermost user frame, or
    None when the trace carries no usable frame (e.g. synthesized eqns)."""
    tb = eqn.source_info.traceback
    for fr in (tb.frames if tb is not None else ()):  # innermost first
        if not any(p in fr.file_name for p in _NOISE_PARTS):
            return fr.file_name, int(fr.line_num), fr.function_name
    return None


def eqn_location(eqn) -> str:
    loc = user_location(eqn)
    return f"{loc[0]}:{loc[1]}" if loc else ""


def location_from_exception(exc: BaseException) -> str:
    """Innermost non-library frame of an exception's traceback — used to
    point a concretization error (rule R2) at the offending hook line."""
    tb = exc.__traceback__
    best = ""
    while tb is not None:
        fname = tb.tb_frame.f_code.co_filename
        if not any(p in fname for p in _NOISE_PARTS):
            best = f"{fname}:{tb.tb_lineno}"
        tb = tb.tb_next
    return best


# --------------------------------------------------------------------------
# eqn iteration (recursive over sub-jaxprs)
# --------------------------------------------------------------------------
def _as_jaxpr(obj):
    if isinstance(obj, _core.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, _core.Jaxpr):
        return obj
    return None


def _sub_jaxprs(eqn) -> Iterator["_core.Jaxpr"]:
    for val in eqn.params.values():
        j = _as_jaxpr(val)
        if j is not None:
            yield j
        elif isinstance(val, (tuple, list)):
            for item in val:
                j = _as_jaxpr(item)
                if j is not None:
                    yield j


def iter_eqns(jaxpr) -> Iterator:
    """Yield every eqn of ``jaxpr`` (a Jaxpr or ClosedJaxpr), recursing
    into the sub-jaxprs held in eqn params."""
    j = _as_jaxpr(jaxpr)
    if j is None:
        return
    for eqn in j.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


# --------------------------------------------------------------------------
# forward taint propagation
# --------------------------------------------------------------------------
def taint_jaxpr(jaxpr, in_taint: Sequence[bool]) -> List[bool]:
    """Per-outvar taint flags for ``jaxpr`` given per-invar flags.

    An output is tainted when its value can depend — through data flow or
    through tainted control flow (a ``cond`` index / ``while`` predicate)
    — on a tainted input.  Constvars and literals are never tainted.
    """
    j = _as_jaxpr(jaxpr)
    assert len(in_taint) == len(j.invars), (len(in_taint), len(j.invars))
    tainted = {v for v, f in zip(j.invars, in_taint) if f}

    def flag(v) -> bool:
        return not isinstance(v, _core.Literal) and v in tainted

    for eqn in j.eqns:
        in_flags = [flag(v) for v in eqn.invars]
        for v, f in zip(eqn.outvars, _eqn_taint(eqn, in_flags)):
            if f:
                tainted.add(v)
    return [flag(v) for v in j.outvars]


def _closed_taint(closed, in_flags: Sequence[bool]) -> List[bool]:
    """Taint through a ClosedJaxpr: its consts are untainted by
    definition, ``in_flags`` covers the explicit invars only."""
    return taint_jaxpr(closed, list(in_flags))


def _fixpoint_loop_taint(body, const_flags, carry_flags,
                         n_extra_in=0, extra_in_flags=()):
    """Iterate body-taint to a fixpoint over the loop carry.  Returns the
    stable carry flags (monotone, so this terminates in <= len(carry)
    rounds)."""
    carry = list(carry_flags)
    for _ in range(len(carry) + 1):
        out = _closed_taint(
            body, list(const_flags) + carry + list(extra_in_flags))
        new = [a or b for a, b in zip(carry, out[:len(carry)])]
        if new == carry:
            return new, out
        carry = new
    return carry, out  # pragma: no cover - monotone, bounded above


def _eqn_taint(eqn, in_flags: List[bool]) -> List[bool]:
    prim = eqn.primitive.name
    n_out = len(eqn.outvars)
    params = eqn.params

    if prim == "cond":
        branches = params["branches"]
        op_flags = in_flags[1:]
        out = [False] * n_out
        for br in branches:
            for i, f in enumerate(_closed_taint(br, op_flags)):
                out[i] = out[i] or f
        if in_flags[0]:  # tainted branch index: control dependence
            out = [True] * n_out
        return out

    if prim == "while":
        cn = params["cond_nconsts"]
        bn = params["body_nconsts"]
        cflags = in_flags[:cn]
        bflags = in_flags[cn:cn + bn]
        carry0 = in_flags[cn + bn:]
        carry, _ = _fixpoint_loop_taint(params["body_jaxpr"], bflags, carry0)
        pred = _closed_taint(params["cond_jaxpr"], cflags + carry)
        if pred and pred[0]:  # tainted trip count: control dependence
            return [True] * n_out
        return carry

    if prim == "scan":
        nc = params["num_consts"]
        ncar = params["num_carry"]
        consts = in_flags[:nc]
        carry0 = in_flags[nc:nc + ncar]
        xs = in_flags[nc + ncar:]
        carry, out = _fixpoint_loop_taint(params["jaxpr"], consts, carry0,
                                          extra_in_flags=xs)
        # outputs: final carry then stacked ys (ys keep the body's flags)
        return carry + out[ncar:]

    # call-like primitives whose inner jaxpr binds the eqn invars 1:1
    for key in ("jaxpr", "call_jaxpr"):
        inner = params.get(key)
        j = _as_jaxpr(inner)
        if j is not None and len(j.invars) == len(in_flags):
            return _closed_taint(inner, in_flags)

    # opaque fallback (pallas_call, scatter, ffi, ...): sound smear
    if any(in_flags):
        return [True] * n_out
    return [False] * n_out
