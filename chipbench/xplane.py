"""Reduce a JAX profiler trace (``.xplane.pb``) to per-device numbers.

A device operation is an event of a device plane's ``XLA Ops`` line
(``/device:TPU:<n>``), or, where the backend runs its ops on host threads
(the CPU backend), an event that carries the ``hlo_op`` and
``device_ordinal`` stats. The ``Async XLA Ops`` line holds the spans of
asynchronous ops, from start to done. Host spans are the events that the
benchmark's own ``jax.profiler.TraceAnnotation`` calls write on the host
plane.

Everything is clipped to the window, the host span named ``window``:

* ``busy_s``: the union of the device's operation intervals and of its
  asynchronous collectives (other asynchronous copies, such as a
  prefetch across programs, overlap idle time and are left out);
* ``op_s``: the summed duration of each operation, by its short name;
* ``collective_s``: the union of the intervals of the operations whose
  HLO opcode is a collective (``COLLECTIVES``), asynchronous ones from
  start to done; ``other_s``: the summed durations of all other
  operations;
* ``gaps``: the idle intervals between operations, each labelled by the
  innermost host span that covers its middle (``call``, ``step``), or by
  what the caller names the time outside them.
"""
from __future__ import annotations

import re
import warnings
from pathlib import Path

# HLO opcodes, and the names of the JAX primitives that the CPU backend
# gives its op events
COLLECTIVES = ("all-to-all", "collective-permute", "all-reduce", "reduce-scatter",
               "all-gather", "collective-broadcast", "ragged-all-to-all",
               "ppermute", "all_to_all", "psum", "psum_scatter", "reduce_scatter",
               "all_gather")
_SUFFIX = re.compile(r"(?:-start|-done|-update)?(?:\.\d+)*$")
# "%fusion.1 = f32[169343,128]{1,0:T(8,128)} fusion(...), kind=kCustom, ..."
_HLO_TEXT = re.compile(r"^%?(?P<name>[\w.-]+) = (?P<shape>.+?) (?P<op>[a-z][\w-]*)\(")
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")


def _bare(name: str) -> str:
    return _SUFFIX.sub("", name.lstrip("%"))


def describe(text: str, hlo_op: str = "") -> tuple:
    """(short name, opcode) of an op event, whose name is an HLO
    instruction's text on a TPU and its bare name on the CPU backend."""
    m = _HLO_TEXT.match(text)
    if not m:
        name = text.lstrip("%")
        return name, _bare(hlo_op or name)
    op = m["op"]
    if op.startswith("async-"):  # a wrapped op: its name says which
        op = hlo_op or m["name"]
    shape = re.sub(r"\{[^{}]*\}", "", m["shape"])
    return f"{m['name']} ({m['op']} {shape})"[:120], _bare(op)


def is_collective(text: str, hlo_op: str = "") -> bool:
    return describe(text, hlo_op)[1] in COLLECTIVES


def _stats(event) -> dict:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def read_events(path) -> tuple:
    """(device ops by device id, host spans). An op is (short name,
    collective?, start_ns, end_ns, asynchronous?); a host span is (name,
    start_ns, end_ns), from every host line."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: dict = {}
    spans = []

    def add(dev, e, hlo_op, is_async):
        short, op = describe(e.name, hlo_op)
        ops.setdefault(dev, []).append((short, op in COLLECTIVES, e.start_ns,
                                        e.start_ns + e.duration_ns, is_async))

    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "Async XLA Ops"):
                for e in line.events:
                    add(int(m.group(1)), e, str(_stats(e).get("hlo_op", "")),
                        line.name == "Async XLA Ops")
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(("$", "ThreadpoolListener")):
                        continue
                    st = _stats(e)
                    if "hlo_op" in st and "device_ordinal" in st:
                        add(int(st["device_ordinal"]), e, str(st["hlo_op"]), False)
                    elif e.duration_ns > 0:
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return ops, spans


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(t: float, spans, outside: str) -> str:
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else outside


def reduce(path, labels=("call", "step"), outside: str = "outside") -> dict:
    """Per-device numbers over the ``window`` span of the trace at ``path``
    (a ``.xplane.pb`` file, or a directory searched for the newest one).
    Idle gaps are named by the innermost host span of ``labels`` around
    them, or ``outside``."""
    path = Path(path)
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    ops, spans = read_events(path)
    windows = [(s, e) for name, s, e in spans if name == "window"]
    if len(windows) != 1:
        raise ValueError(f"trace has {len(windows)} 'window' spans, not 1")
    w0, w1 = windows[0]
    inner = [sp for sp in spans if sp[0] in labels and sp[2] >= w0 and sp[1] <= w1]
    devices = {}
    for dev, evs in sorted(ops.items()):
        clipped = [(n, c, max(s, w0), min(e, w1), a) for n, c, s, e, a in evs
                   if e > w0 and s < w1]
        sync = [ev for ev in clipped if not ev[4]]
        busy = _union([(s, e) for _, c, s, e, a in clipped if c or not a])
        op_s: dict = {}
        for n, _, s, e, _ in sync:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
        coll = _union([(s, e) for _, c, s, e, _ in clipped if c])
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps = [(_label((a + b) / 2, inner, outside), (b - a) * 1e-9)
                for a, b in zip(edges[::2], edges[1::2]) if b > a]
        devices[dev] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "op_s": op_s,
            "collective_s": sum(e - s for s, e in coll) * 1e-9,
            "other_s": sum(e - s for _, c, s, e, _ in sync if not c) * 1e-9,
            "gaps": gaps}
    return {"window_s": (w1 - w0) * 1e-9, "devices": devices, "file": str(path)}
