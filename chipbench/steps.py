"""The executors' steps in a JAX profiler trace (``.xplane.pb``).

Inside their ``shard_map`` bodies the executors run each step under a
``jax.named_scope`` of its own, below ``shiro.spmm`` and without the
``shiro.`` prefix: ``pack``, ``exchange``, ``compute``, ``aggregate``
(``STEPS``). ``scopes.scope_of`` keeps only the outermost ``shiro.*``
scope of an op's ``op_name``; this module keeps the innermost step under
it too. Op names are found as ``scopes`` finds them: on the op event's
own stats, else in the HLO modules stored in the trace.

``reduce(path)`` gives, clipped to the ``window`` span, ``step_s``: per
device, the summed duration of the synchronous ops by (scope, step), the
scope as ``scopes.scope_of`` gives it and the step None for an op under
no step. Summed over the steps, a scope's seconds are ``scopes.reduce``'s
``scope_s`` for it. A program that names no steps gives None for every
step, and the readers of ``metrics/`` then return None.

The names are written here and not imported from the program, so that a
program without them is read without error.
"""
from __future__ import annotations

from pathlib import Path

from chipbench import scopes, xplane

STEPS = ("pack", "exchange", "compute", "aggregate")
_cache: dict = {}


def step_of(op_name: str) -> str | None:
    """The innermost step under the outermost ``shiro.*`` scope, or None:
    ``pack`` for ``jit(call)/shiro.spmm/shard_map/pack/gather``."""
    step, inside = None, False
    for part in op_name.split("/"):
        part = scopes._unwrapped(part)
        if part.startswith(scopes.PREFIX):
            inside = True
        elif inside and part in STEPS:
            step = part
    return step


def _read(path) -> tuple:
    """(synchronous device ops by device, window spans): an op is
    ((scope, step), start_ns, end_ns), a window (start_ns, end_ns)."""
    from jax.profiler import ProfileData

    names = scopes.hlo_op_names(path)
    pd = ProfileData.from_file(str(path))
    ops: dict = {}
    windows = []

    def key(e, st, modules):
        op_name = str(st.get("tf_op") or st.get("op_name") or "")
        if not op_name:
            program = st.get("program_id")
            module = st.get("hlo_module")
            if program is None and module is None and modules:
                program = scopes._enclosing(modules, e.start_ns)
            table = names.get(program) or names.get(str(module)) or {}
            op_name = table.get(scopes._instruction(e.name, st), "")
        return scopes.scope_of(op_name), step_of(op_name)

    for plane in pd.planes:
        m = xplane._DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, scopes._program(e.name))
                             for e in (lines["XLA Modules"].events
                                       if "XLA Modules" in lines else ()))
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                ops.setdefault(int(m.group(1)), []).append(
                    (key(e, xplane._stats(e), modules), e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "window":
                        windows.append((e.start_ns, e.start_ns + e.duration_ns))
                        continue
                    if e.name.startswith(("$", "ThreadpoolListener")):
                        continue
                    st = xplane._stats(e)
                    if "hlo_op" in st and "device_ordinal" in st:
                        ops.setdefault(int(st["device_ordinal"]), []).append(
                            (key(e, st, ()), e.start_ns, e.start_ns + e.duration_ns))
    return ops, windows


def reduce(path) -> dict:
    """Step seconds over the ``window`` span of the trace at ``path`` (a
    ``.xplane.pb`` file or a directory searched for the newest)."""
    path = Path(path)
    if path.is_dir():
        path = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)[-1]
    ops, windows = _read(path)
    if len(windows) != 1:
        raise ValueError(f"trace has {len(windows)} 'window' spans, not 1")
    w0, w1 = windows[0]
    step_s: dict = {}
    for dev, evs in sorted(ops.items()):
        by_step: dict = {}
        for k, s, e in evs:
            if e > w0 and s < w1:
                by_step[k] = by_step.get(k, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
        step_s[dev] = by_step
    return {"step_s": step_s, "file": str(path)}


def of(rec: dict) -> dict | None:
    """``reduce`` of a traced run's record (None without a trace), read
    once per trace file."""
    trace = rec.get("trace")
    if not trace or not trace.get("file") or not Path(trace["file"]).is_file():
        return None
    st = Path(trace["file"]).stat()
    k = (trace["file"], st.st_mtime_ns, st.st_size)
    if k not in _cache:
        _cache.clear()
        _cache[k] = reduce(trace["file"])
    return _cache[k]


def step_ms(rec: dict, scope: str, step: str, unit: str) -> float | None:
    """Device ms per completed unit of the ops under ``shiro.<scope>`` /
    ``step``, mean over the cell's chips; None on one chip (no exchange),
    without a trace, and where no op ran under the step."""
    done = rec["attempted"] - rec["failed"]
    if rec["chips"] == 1 or rec["unit"] != unit or not done:
        return None
    red = of(rec)
    if red is None:
        return None
    s = sum(d.get((scope, step), 0.0) for d in red["step_s"].values())
    return 1e3 * s / rec["chips"] / done if s else None
