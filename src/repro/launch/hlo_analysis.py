"""Post-compile HLO analysis: collective bytes + roofline terms.

``collective_bytes`` two-pass-parses optimized HLO text: first build a
symbol table (instruction name → result byte size), then sum OPERAND
sizes for every collective op (all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, including their -start variants; -done
ops are skipped so async pairs are not double-counted).

``roofline`` combines cost_analysis + collective bytes into the three
terms of EXPERIMENTS.md §Roofline. Hardware constants: TPU v5e-class
chip — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI (assignment).
``cost_analysis`` of an SPMD-partitioned executable reports PER-DEVICE
flops/bytes, so terms are per-chip by construction (equivalent to the
assignment's global/(chips·peak) form).
"""
from __future__ import annotations

import re
from typing import Dict, Optional

__all__ = ["DTYPE_BYTES", "parse_shape_bytes", "collective_bytes",
           "collective_rows", "roofline", "executable_memory",
           "strip_metadata", "HW"]

HW = {
    "peak_flops": 197e12,  # bf16 FLOP/s per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link
    "dcn_bw": 6.25e9,  # bytes/s per chip, inter-pod
}

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "all-gather-start", "all-reduce-start",
    "collective-permute-start", "ragged-all-to-all",
)
_DONE = ("all-gather-done", "all-reduce-done", "collective-permute-done")
# XLA prints ``/*index=5*/`` markers inside long operand and type lists
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_METADATA_RE = re.compile(r", metadata=\{[^{}]*\}")
_FRAME_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip_metadata(hlo_text: str) -> str:
    """HLO text without source metadata.

    Drops every instruction's ``metadata={...}`` and the stack-frame
    tables printed after the module header. Those carry the Python file,
    line and column each op was traced from, so two identical programs
    lowered from different call sites differ only there.
    """
    lines, in_table = [], False
    for line in hlo_text.splitlines():
        if line in _FRAME_TABLES:
            in_table = True
        elif in_table and not line.strip():
            in_table = False
        elif not in_table:
            lines.append(_METADATA_RE.sub("", line))
    return "\n".join(lines) + "\n"


def parse_shape_bytes(type_str: str) -> int:
    """Bytes of an HLO result type (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind operand bytes, plus 'total'."""
    sizes: Dict[str, int] = {}
    colls = []
    for line in hlo_text.splitlines():
        line = _COMMENT_RE.sub("", line)
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.groups()
        sizes[name] = parse_shape_bytes(type_str)
        if opcode in _COLLECTIVES and opcode not in _DONE:
            # operand list: first parenthesized group after the opcode
            rest = line.split(opcode + "(", 1)[1]
            depth, args = 1, ""
            for ch in rest:
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        break
                args += ch
            ops = [a.strip().lstrip("%") for a in args.split(",") if a.strip()]
            colls.append((opcode, name, ops))

    out: Dict[str, int] = {}
    for opcode, name, ops in colls:
        b = 0
        for o in ops:
            o = o.split(" ")[-1].lstrip("%")
            if o in sizes:
                b += sizes[o]
        if b == 0:  # fallback: use result size
            b = sizes.get(name, 0)
        key = opcode.replace("-start", "")
        out[key] = out.get(key, 0) + b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def collective_rows(coll: Dict[str, int], n_dense: int,
                    sz_dt: int = 4) -> float:
    """Convert measured per-device collective bytes into buffer rows.

    The SHIRO executors only move [rows, n_dense] float payloads through
    their collectives, so ``total / (n_dense · sz)`` is the per-device
    padded row count — directly comparable to
    ``SpmmPlan.volume_rows_padded(schedule) / P`` when verifying that a
    schedule's executed bytes match the planner's accounting.
    """
    return coll.get("total", 0) / float(n_dense * sz_dt)


_MEMORY_FIELDS = (
    "argument_size_in_bytes", "output_size_in_bytes", "temp_size_in_bytes",
    "generated_code_size_in_bytes", "alias_size_in_bytes",
)


def executable_memory(compiled) -> Dict[str, int]:
    """Per-device allocation profile of an AOT-compiled computation.

    Reads ``compiled.memory_analysis()`` (XLA ``CompiledMemoryStats``)
    and adds ``total_allocation_size`` = arguments + outputs + temps +
    generated code − aliased bytes, i.e. what the executable actually
    pins per device — donated/aliased operands are counted once. Returns
    ``{}`` when the backend exposes no memory stats (older plugins),
    so callers can treat the numbers as best-effort.
    """
    try:
        stats = compiled.memory_analysis()
    except Exception:  # pragma: no cover — backend without the API
        return {}
    if stats is None:  # pragma: no cover
        return {}
    out: Dict[str, int] = {}
    for field in _MEMORY_FIELDS:
        v = getattr(stats, field, None)
        if v is not None:
            out[field] = int(v)
    if not out:  # pragma: no cover — unexpected stats object
        return {}
    out["total_allocation_size"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        + out.get("generated_code_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0))
    return out


def roofline(cost: dict, coll: Dict[str, int], *, chips: int,
             model_flops: Optional[float] = None,
             steps_per_call: int = 1) -> dict:
    """Three roofline terms (seconds) + bottleneck + useful-flops ratio.

    ``cost`` = compiled.cost_analysis() (per-device). ``model_flops`` =
    6·N·D-style global useful flops for the call, if known.
    """
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    cbytes = float(coll.get("total", 0))
    t_compute = flops / HW["peak_flops"]
    t_memory = bytes_acc / HW["hbm_bw"]
    t_collective = cbytes / HW["ici_bw"]
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    bottleneck = max(terms, key=terms.get)
    out = {
        **terms,
        "bottleneck": bottleneck,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_acc,
        "collective_bytes_per_chip": cbytes,
        "bound_time": max(terms.values()),
    }
    if model_flops:
        total_hlo = flops * chips
        out["model_flops"] = model_flops
        out["useful_flops_ratio"] = (model_flops / total_hlo
                                     if total_hlo else 0.0)
        # roofline fraction: useful work / (what the dominant term costs)
        t_ideal = model_flops / (chips * HW["peak_flops"])
        out["roofline_fraction"] = (t_ideal / out["bound_time"]
                                    if out["bound_time"] else 0.0)
    return out
