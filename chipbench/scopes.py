"""The program's own names in a JAX profiler trace (``.xplane.pb``).

``xplane.reduce`` names device ops by their HLO instruction and idle gaps
by the harness's spans. This module reads what the program itself writes:

* device scopes: each op's ``op_name`` metadata carries the
  ``jax.named_scope`` path it was traced under. It is taken from the op
  event's own stats where the trace has them (``tf_op``, ``op_name``),
  else from the HLO modules that the profiler stores in the trace's
  ``/host:metadata`` plane, keyed by program id (or module name) and
  instruction name. A fusion without metadata of its own takes its fused
  root's. ``scope_of`` reduces a path to its outermost ``shiro.*`` scope,
  ``spmm`` for ``shiro.spmm`` (JAX's ``jvp(...)`` and ``transpose(...)``
  wrappers removed, so a backward pass counts as its scope), or ``other``;
* program spans: host spans whose name starts with ``shiro.``, and the
  numbers they carry (``shiro.guard``'s ``host_bytes``).

``reduce(path)`` gives, clipped to the ``window`` span:

* ``scope_s``: per device, the summed duration of the synchronous ops by
  scope (as ``op_s`` sums them by name);
* ``program_spans_s``: host seconds by program span name;
* ``span_stats``: per program span name, the numeric stats of the spans
  that started in the window, summed.

A trace of a program that writes none of these gives empty mappings.
"""
from __future__ import annotations

import re
from pathlib import Path

from chipbench import xplane

PREFIX = "shiro."
OTHER = "other"
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
_MODULE_EVENT = re.compile(r"^(?P<name>.+)\((?P<id>\d+)\)$")
_cache: dict = {}


def scope_of(op_name: str) -> str:
    """``spmm`` for ``jit(step)/transpose(jvp(shiro.spmm))/mul``; ``other``
    outside every ``shiro.*`` scope."""
    for part in op_name.split("/"):
        part = _unwrapped(part)
        if part.startswith(PREFIX):
            return part[len(PREFIX):]
    return OTHER


def _unwrapped(part: str) -> str:
    while (m := _WRAPPED.match(part)):
        part = m.group(1)
    return part


# --- protocol buffers, read by hand: the few fields this module needs ----

def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint or fixed field, a memoryview for a length-delimited one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        shift = out = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            if b < 0x80:
                return out
            shift += 7

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, varint()
        elif wire == 2:
            size = varint()
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            yield field, int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            yield field, int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} is not read here")


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> list:
    """A repeated int64 field's value: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, cur, shift = [], 0, 0
    for b in bytes(v):
        cur |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            out.append(cur)
            cur = shift = 0
    return out


def _hlo_op_names(hlo_proto) -> dict:
    """Instruction name -> op_name of one serialized ``HloProto``; a fusion
    (or other caller) without metadata takes its called computations'
    root's."""
    computations = {}  # id -> (root id, {instruction id: (name, op_name, callees)})
    for f, module in _fields(hlo_proto):
        if f != 1:  # HloProto.hlo_module
            continue
        for g, comp in _fields(module):
            if g != 3:  # HloModuleProto.computations
                continue
            cid = root = None
            instrs = {}
            for h, v in _fields(comp):
                if h == 5:
                    cid = v
                elif h == 6:
                    root = v
                elif h == 2:  # HloComputationProto.instructions
                    name, op_name, iid, callees = "", "", None, []
                    for k, w in _fields(v):
                        if k == 1:
                            name = _text(w)
                        elif k == 7:  # OpMetadata
                            for m, x in _fields(w):
                                if m == 2:
                                    op_name = _text(x)
                        elif k == 35:
                            iid = w
                        elif k == 38:
                            callees += _ints(w)
                    instrs[iid] = (name, op_name, callees)
            computations[cid] = (root, instrs)

    def root_op_name(cid, depth=0):
        if cid not in computations or depth > 8:
            return ""
        root, instrs = computations[cid]
        if root not in instrs:
            return ""
        _, op_name, callees = instrs[root]
        for callee in callees:
            if op_name:
                break
            op_name = root_op_name(callee, depth + 1)
        return op_name

    out = {}
    for _root, instrs in computations.values():
        for name, op_name, callees in instrs.values():
            for callee in callees:
                if op_name:
                    break
                op_name = root_op_name(callee)
            out[name] = op_name
    return out


def hlo_op_names(path) -> dict:
    """``{program id: {instruction: op_name}}``, and the same tables under
    the module's name, from the HLO modules in the trace's
    ``/host:metadata`` plane."""
    data = Path(path).read_bytes()
    out = {}
    for f, plane in _fields(data):
        if f != 1:  # XSpace.planes
            continue
        # a plane's name (field 2) comes before its lines and metadata
        if next((_text(v) for g, v in _fields(plane) if g == 2), "") != "/host:metadata":
            continue
        fields = list(_fields(plane))
        stat_names = {}
        events = []
        for g, v in fields:
            if g == 5:  # stat_metadata: map<int64, XStatMetadata>
                for k, w in _fields(v):
                    if k == 2:
                        meta = dict(_fields(w))
                        stat_names[meta.get(1)] = _text(meta.get(2, b""))
            elif g == 4:  # event_metadata: map<int64, XEventMetadata>
                for k, w in _fields(v):
                    if k == 2:
                        events.append(w)
        for ev in events:
            name, protos = "", []
            for k, w in _fields(ev):
                if k == 2:
                    name = _text(w)
                elif k == 5:  # XStat
                    stat = dict(_fields(w))
                    if stat_names.get(stat.get(1)) == "Hlo Proto" and 6 in stat:
                        protos.append(stat[6])
            for proto in protos:
                names = _hlo_op_names(proto)
                m = _MODULE_EVENT.match(name)
                out[m["name"] if m else name] = names
                if m:
                    out[int(m["id"])] = names
    return out


# --- the reduction ----------------------------------------------------------

def _instruction(text: str, stats: dict) -> str:
    m = xplane._HLO_TEXT.match(text)
    if m:
        return m["name"]
    return str(stats.get("hlo_op") or text).lstrip("%")


def _read(path) -> tuple:
    """(device ops, host spans): a synchronous op is (scope, start_ns,
    end_ns), a span (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    names = hlo_op_names(path)
    pd = ProfileData.from_file(str(path))
    ops: dict = {}
    spans = []

    def scope(e, st, modules):
        op_name = str(st.get("tf_op") or st.get("op_name") or "")
        if not op_name:
            program = st.get("program_id")
            module = st.get("hlo_module")
            if program is None and module is None and modules:
                program = _enclosing(modules, e.start_ns)
            table = names.get(program) or names.get(str(module)) or {}
            op_name = table.get(_instruction(e.name, st), "")
        return scope_of(op_name)

    for plane in pd.planes:
        m = xplane._DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, _program(e.name))
                             for e in (lines["XLA Modules"].events
                                       if "XLA Modules" in lines else ()))
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                ops.setdefault(int(m.group(1)), []).append(
                    (scope(e, xplane._stats(e), modules), e.start_ns,
                     e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("$", "ThreadpoolListener")):
                        continue
                    st = xplane._stats(e)
                    if "hlo_op" in st and "device_ordinal" in st:
                        ops.setdefault(int(st["device_ordinal"]), []).append(
                            (scope(e, st, ()), e.start_ns, e.start_ns + e.duration_ns))
                    elif e.duration_ns > 0:
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns, st))
    return ops, spans


def _program(name: str):
    m = _MODULE_EVENT.match(name)
    return int(m["id"]) if m else name


def _enclosing(modules, t):
    for s, e, program in modules:
        if s <= t < e:
            return program
    return None


def reduce(path) -> dict:
    """The program's names over the ``window`` span of the trace at
    ``path`` (a ``.xplane.pb`` file or a directory searched for the newest)."""
    path = Path(path)
    if path.is_dir():
        path = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)[-1]
    ops, spans = _read(path)
    windows = [(s, e) for name, s, e, _ in spans if name == "window"]
    if len(windows) != 1:
        raise ValueError(f"trace has {len(windows)} 'window' spans, not 1")
    w0, w1 = windows[0]
    program = [sp for sp in spans
               if sp[0].startswith(PREFIX) and sp[2] >= w0 and sp[1] <= w1]
    spans_s: dict = {}
    stats: dict = {}
    for name, s, e, st in program:
        spans_s[name] = spans_s.get(name, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
        if s >= w0:
            into = stats.setdefault(name, {})
            for k, v in st.items():
                if isinstance(v, (int, float)) and not k.startswith("_"):
                    into[k] = into.get(k, 0) + v
    scope_s: dict = {}
    for dev, evs in sorted(ops.items()):
        by_scope: dict = {}
        for n, s, e in evs:
            if e > w0 and s < w1:
                by_scope[n] = by_scope.get(n, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
        scope_s[dev] = by_scope
    return {"scope_s": scope_s, "program_spans_s": spans_s, "span_stats": stats,
            "file": str(path)}


def of(rec: dict) -> dict | None:
    """``reduce`` of a traced run's record (None without a trace), read
    once per trace file."""
    trace = rec.get("trace")
    if not trace or not trace.get("file") or not Path(trace["file"]).is_file():
        return None
    st = Path(trace["file"]).stat()
    key = (trace["file"], st.st_mtime_ns, st.st_size)
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce(trace["file"])
    return _cache[key]


# --- helpers of the metric readers ------------------------------------------

def _per_unit(rec: dict, unit: str):
    """(reduction, completed units), or None where a reader has nothing to
    read: another unit's cell, no trace, or no completed unit."""
    done = rec["attempted"] - rec["failed"]
    if rec["unit"] != unit or not done:
        return None
    red = of(rec)
    return None if red is None else (red, done)


def span_ms(rec: dict, name: str, unit: str) -> float | None:
    """Host ms per completed unit inside the program span ``name``."""
    got = _per_unit(rec, unit)
    if got is None or name not in got[0]["program_spans_s"]:
        return None
    return 1e3 * got[0]["program_spans_s"][name] / got[1]


def span_stat(rec: dict, name: str, stat: str, unit: str) -> float | None:
    """The program span ``name``'s number ``stat``, summed over the window,
    per completed unit."""
    got = _per_unit(rec, unit)
    if got is None or stat not in got[0]["span_stats"].get(name, {}):
        return None
    return got[0]["span_stats"][name][stat] / got[1]


def scope_ms(rec: dict, top: str, unit: str) -> float | None:
    """Device ms per completed unit of the ops under the ``shiro.<top>``
    scope, mean over the cell's chips; None where no op is under it."""
    got = _per_unit(rec, unit)
    if got is None:
        return None
    s = sum(d.get(top, 0.0) for d in got[0]["scope_s"].values())
    return 1e3 * s / rec["chips"] / got[1] if s else None
