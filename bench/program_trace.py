"""The program's own spans and scopes in a JAX profiler trace.

``bench/trace.py`` names device time by HLO instruction and idle gaps by
the innermost host event; this module reads what the library itself puts
in the same ``.xplane.pb`` (``repro.core.spans``): host spans named
``graphyti.*`` with integer keyword arguments, and device ops under
``jax.named_scope("graphyti.*")``.  ``reduce_program`` computes, inside
the window of one host span:

* ``spans``: per program span name, its ``count``, ``total_s``,
  ``self_s`` (duration less what its child program spans cover, on the
  same thread) and ``args``, the sum of each integer keyword argument as
  an exact Python int;
* ``device_scopes``: device self time per innermost ``graphyti.*`` scope,
  averaged over the devices like ``busy_s``; an op nested in another (the
  body of a ``while``) is counted once, an op the compiler added without a
  framework name (a relayout inside a scan's loop) takes the scope of the
  op it runs in, and time no scope names goes to ``other``, so the values
  sum to the busy time;
* ``idle_under``: device-idle time per innermost host event over each
  idle gap's midpoint, summed over every gap (``trace.reduce_events``
  names the ten longest the same way), so the values sum to the window's
  idle time.

An op's scope is the ``tf_op`` stat of its event metadata, for example
``jit(seg)/while/body/graphyti.gather/graphyti.chunk_scan/while/body/add``.
The profiler leaves that stat out for control flow (``while``,
``conditional``); there the scope is the ``op_name`` of the HLO
instruction in the program's ``HloProto``, which the ``/host:metadata``
plane holds under the program id, joined on ``(program_id, instruction
name)``.  ``jax.profiler.ProfileData`` returns neither, so
``read_program_trace`` decodes the file's protobuf wire format itself
(the schemas of ``tsl/profiler/protobuf/xplane.proto`` and
``xla/service/hlo.proto``), needing nothing beyond the standard library.
"""
from __future__ import annotations

import heapq
import re
import struct
from typing import NamedTuple, Optional

from .trace import DEVICE_PREFIX, HOST_PLANE, OPS_LINE, union

METADATA_PLANE = "/host:metadata"
PREFIX = "graphyti."
OTHER = "other"
_SCOPE = re.compile(r"graphyti\.[A-Za-z0-9_]+")


class Op(NamedTuple):
    scope: Optional[str]  # innermost graphyti.* scope, OTHER, or None
    #                       where the op has no framework name
    start_ns: float
    end_ns: float


class Span(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    thread: int  # the host line (one per thread)
    args: dict  # integer stats by name


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------
def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if x < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, i: int, end: int):
    """``(field number, value)`` of the message in ``b[i:end]``: an int for
    varint and fixed fields, ``(start, end)`` for length-delimited ones."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wire == 1:
            v = int.from_bytes(b[i:i + 8], "little")
            i += 8
        elif wire == 5:
            v = int.from_bytes(b[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _stat(b: bytes, span, stat_names: dict):
    """``(name, value)`` of an XStat: ints for int64/uint64, str for
    str_value and ref_value, float for double_value."""
    sid, value = None, None
    for f, v in _fields(b, *span):
        if f == 1:
            sid = v
        elif f == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif f == 5:
            value = _text(b, v)
        elif f == 7:
            value = stat_names.get(v, "")
    return stat_names.get(sid, ""), value


def _map_values(b: bytes, span):
    """The value messages of a protobuf map entry list (field 2 of each)."""
    for f, v in _fields(b, *span):
        if f == 2:
            yield v


def _plane(b: bytes, span):
    """``(name, lines, event metadata, stat names)`` of one XPlane; an
    event metadata maps its id to ``(name, [stat spans])``."""
    name, lines, meta_spans, stat_names = "", [], [], {}
    for f, v in _fields(b, *span):
        if f == 2:
            name = _text(b, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta_spans.append(v)
        elif f == 5:
            for entry in _map_values(b, v):
                sid, sname = 0, ""
                for ef, ev in _fields(b, *entry):
                    if ef == 1:
                        sid = ev
                    elif ef == 2:
                        sname = _text(b, ev)
                stat_names[sid] = sname
    meta = {}
    for v in meta_spans:
        for entry in _map_values(b, v):
            mid, mname, stats = 0, "", []
            for ef, ev in _fields(b, *entry):
                if ef == 1:
                    mid = ev
                elif ef == 2:
                    mname = _text(b, ev)
                elif ef == 5:
                    stats.append(ev)
            meta[mid] = (mname, stats)
    return name, lines, meta, stat_names


def _line(b: bytes, span):
    """``(line id, name, timestamp_ns, [event spans])`` of one XLine."""
    lid, name, ts, events = 0, "", 0, []
    for f, v in _fields(b, *span):
        if f == 1:
            lid = v
        elif f == 2:
            name = _text(b, v)
        elif f == 3:
            ts = v
        elif f == 4:
            events.append(v)
    return lid, name, ts, events


def _event(b: bytes, span):
    """``(metadata id, offset_ps, duration_ps, [stat spans])`` of an
    XEvent."""
    mid = off = dur = 0
    stats = []
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            off = v
        elif f == 3:
            dur = v
        elif f == 4:
            stats.append(v)
    return mid, off, dur, stats


def innermost_scope(tf_op: Optional[str]) -> Optional[str]:
    """The last ``graphyti.*`` component of an op's framework name;
    ``None`` for an op without one."""
    if not tf_op:
        return None
    found = _SCOPE.findall(tf_op)
    return found[-1] if found else OTHER


def _hlo_op_names(b: bytes, meta: dict) -> dict:
    """``{program id: {HLO instruction name: op_name}}`` from the event
    metadata of the ``/host:metadata`` plane (id: the program id; stat: its
    serialized ``HloProto``)."""
    out: dict = {}
    for pid, (_, stats) in meta.items():
        names = out.setdefault(pid, {})
        for st in stats:
            proto = dict(_fields(b, *st)).get(6)  # XStat.bytes_value
            for f, module in _fields(b, *proto) if proto else ():
                if f != 1:  # HloProto.hlo_module
                    continue
                for f, comp in _fields(b, *module):
                    if f != 3:  # HloModuleProto.computations
                        continue
                    for f, inst in _fields(b, *comp):
                        if f != 2:  # HloComputationProto.instructions
                            continue
                        name, op_name = "", ""
                        for fi, vi in _fields(b, *inst):
                            if fi == 1:
                                name = _text(b, vi)
                            elif fi == 7:  # OpMetadata
                                op_name = next((_text(b, vm) for fm, vm
                                                in _fields(b, *vi)
                                                if fm == 2), "")
                        if op_name:
                            names[name] = op_name
    return out


def read_program_trace(path: str) -> tuple[dict, list]:
    """``({device plane: [Op]}, [Span])`` from a trace file: the ops of each
    device plane's ``XLA Ops`` line (all its lines where it has none, as in
    ``trace.read_xplane``) with their scopes, and every host event, with
    the integer arguments of the program's own."""
    with open(path, "rb") as f:
        b = f.read()
    devices: dict = {}
    host: list = []
    planes = [_plane(b, s) for f, s in _fields(b, 0, len(b)) if f == 1]
    op_names = next((_hlo_op_names(b, meta) for name, _, meta, _ in planes
                     if name == METADATA_PLANE), {})
    for name, lines, meta, stat_names in planes:
        if name.startswith(DEVICE_PREFIX):
            decoded = [_line(b, s) for s in lines]
            ops_lines = [ln for ln in decoded if ln[1] == OPS_LINE] or decoded
            scope = {}
            for mid, (mname, stats) in meta.items():
                st = dict(_stat(b, s, stat_names) for s in stats)
                scope[mid] = innermost_scope(
                    st.get("tf_op") or op_names.get(st.get("program_id"), {})
                    .get(mname.split(" = ", 1)[0].lstrip("%")))
            ops = []
            for _, _, ts, events in ops_lines:
                for es in events:
                    mid, off, dur, _ = _event(b, es)
                    start = ts + off / 1e3
                    ops.append(Op(scope.get(mid), start, start + dur / 1e3))
            devices[name] = ops
        elif name == HOST_PLANE:
            def int_stats(spans):
                return {k: v for k, v in (_stat(b, s, stat_names)
                                          for s in spans)
                        if isinstance(v, int)}

            meta_args = {mid: int_stats(st) for mid, (mname, st)
                         in meta.items() if mname.startswith(PREFIX)}
            for s in lines:
                lid, _, ts, events = _line(b, s)
                for es in events:
                    mid, off, dur, stats = _event(b, es)
                    # Only program spans carry arguments worth decoding.
                    args = ({**meta_args[mid], **int_stats(stats)}
                            if mid in meta_args else {})
                    start = ts + off / 1e3
                    host.append(Span(meta.get(mid, ("",))[0], start,
                                     start + dur / 1e3, lid, args))
    return devices, host


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------
def self_times(ops, lo: float, hi: float) -> dict:
    """Nanoseconds per scope inside ``[lo, hi]``, each instant given to the
    op that started last among those running (the innermost of nested
    ops), so that the values sum to the union of the ops' intervals.  An op
    whose scope is ``None`` takes that of the op running when it starts
    (``OTHER`` where none is)."""
    out: dict = {}
    stack: list = []  # open ops, (end, scope), the last started on top
    cursor = lo

    def give(scope, until):
        nonlocal cursor
        if until > cursor:
            out[scope] = out.get(scope, 0.0) + until - cursor
            cursor = until

    for op in sorted(ops, key=lambda o: (o.start_ns, -o.end_ns)):
        s, e = max(op.start_ns, lo), min(op.end_ns, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            give(stack[-1][1], stack.pop()[0])
        if stack:
            give(stack[-1][1], s)
        cursor = max(cursor, s)
        scope = op.scope
        if scope is None:
            scope = stack[-1][1] if stack else OTHER
        stack.append((e, scope))
    while stack:
        give(stack[-1][1], stack.pop()[0])
    return out


def _innermost_names(host: list, times: list) -> list:
    """Per time in ``times`` (ascending), the name of the shortest host
    event covering it, the first listed among equals ("no host span" where
    none does): ``trace._innermost`` as one sweep."""
    order = sorted(range(len(host)), key=lambda i: host[i].start_ns)
    heap: list = []  # (duration, index, end)
    k = 0
    names = []
    for t in times:
        while k < len(order) and host[order[k]].start_ns <= t:
            ev = host[order[k]]
            heapq.heappush(heap, (ev.end_ns - ev.start_ns, order[k],
                                  ev.end_ns))
            k += 1
        while heap and heap[0][2] < t:
            heapq.heappop(heap)
        names.append(host[heap[0][1]].name if heap else "no host span")
    return names


def _close(table: dict, stack: list) -> None:
    sp, child = stack.pop()
    row = table.setdefault(sp.name, {"count": 0, "total_s": 0.0,
                                     "self_s": 0.0, "args": {}})
    dur = sp.end_ns - sp.start_ns
    row["count"] += 1
    row["total_s"] += dur * 1e-9
    row["self_s"] += (dur - child) * 1e-9
    for k, v in sp.args.items():
        row["args"][k] = row["args"].get(k, 0) + v


def _span_table(spans: list) -> dict:
    """Count, total, self time and summed integer arguments per name; self
    time is the duration less the direct child spans on the same thread."""
    table: dict = {}
    by_thread: dict = {}
    for sp in spans:
        by_thread.setdefault(sp.thread, []).append(sp)
    for thread_spans in by_thread.values():
        stack: list = []  # [span, ns its direct children cover]
        for sp in sorted(thread_spans, key=lambda s: (s.start_ns,
                                                       -s.end_ns)):
            while stack and stack[-1][0].end_ns <= sp.start_ns:
                _close(table, stack)
            if stack:
                stack[-1][1] += min(sp.end_ns, stack[-1][0].end_ns) \
                    - sp.start_ns
            stack.append([sp, 0.0])
        while stack:
            _close(table, stack)
    return table


def reduce_program(devices: dict, host: list, window_span: str) -> dict:
    """``spans``, ``device_scopes`` and ``idle_under`` in the window of the
    host span named ``window_span`` (see the module docstring).  Raises
    when the span or a device plane is missing, like
    ``trace.reduce_events``."""
    windows = [ev for ev in host if ev.name == window_span]
    if len(windows) != 1:
        raise ValueError(f"expected one host span {window_span!r}, found "
                         f"{len(windows)}")
    if not devices:
        raise ValueError("the trace holds no device plane")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    scope_ns: dict = {}
    gaps: list = []
    for ops in devices.values():
        for scope, ns in self_times(ops, lo, hi).items():
            scope_ns[scope] = scope_ns.get(scope, 0.0) + ns
        merged = union(((o.start_ns, o.end_ns) for o in ops), lo, hi)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    gaps.sort(key=lambda g: g[0] + g[1])
    idle: dict = {}
    names = _innermost_names(host, [(s + e) / 2 for s, e in gaps])
    for name, (s, e) in zip(names, gaps):
        idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9
    program = [sp for sp in host
               if sp.name.startswith(PREFIX) and lo <= sp.start_ns <= hi]
    per_device = 1e-9 / len(devices)
    return {
        "spans": _span_table(program),
        "device_scopes": {k: ns * per_device for k, ns in sorted(
            scope_ns.items(), key=lambda kv: -kv[1])},
        "idle_under": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
    }
