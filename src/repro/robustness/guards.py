"""Numerical and shape guardrails for the serving path.

The failure mode these guard against is not a crash — it is a WRONG
ANSWER served with a straight face: a B with the wrong row count dies
three layers down as a shard_map shape error naming none of the caller's
objects, and a NaN in ``a.data`` propagates into every C row that
touches the poisoned nonzero, silently, forever. ``SpmmConfig.check``
turns the guards on (default ``"auto"``):

  ``False``   no validation — bit-identical to the pre-guardrail tree.
  ``"auto"``  actionable shape/dtype errors on B before XLA sees the
              mismatch, finite-values validation of the sparse operand
              at plan time, and a cheap SAMPLED ``isfinite`` sweep over
              C after each call (corner + strided rows per addressable
              shard).
  ``"full"`` / ``True``  the same, but the C sweep checks every element.

The C sweep runs on the device: each addressable shard is reduced on its
own device by one small jitted probe (``probe_finite``), and the host
reads back only a few scalars per shard (``read_probes``: the local row
and column of the first non-finite element and its value, 12 bytes for
float32), never C itself. Host NumPy arrays are swept on the host.

A failed C sweep raises ``NumericalFault`` naming the first bad element
and the handle call that produced it; ``SpmmWaveServer`` catches it like
any wave failure (retry, then surface), so its message also ends up
naming the first bad wave.
"""
from __future__ import annotations

import functools
from typing import Any, Iterator, List, NamedTuple, Optional, Tuple

import numpy as np

__all__ = [
    "NumericalFault",
    "check_mode",
    "validate_dense_operand",
    "validate_sddmm_operands",
    "validate_sparse_values",
    "validate_pattern",
    "sampled_finite_check",
    "sampled_finite_check_tree",
    "FiniteProbe",
    "probe_finite",
    "compile_probe",
    "read_probes",
    "raise_nonfinite",
]

# rows sampled per addressable block under check="auto"
_SAMPLE_ROWS = 32

_MODES = (False, "auto", "full", True)


class NumericalFault(FloatingPointError):
    """A non-finite value crossed a guarded boundary (C sweep or operand
    validation). Carries enough context to find the producer."""


def check_mode(config) -> Any:
    """The effective ``check`` mode of a config (older pickled configs
    predate the field and mean ``"auto"``)."""
    mode = getattr(config, "check", "auto")
    return "full" if mode is True else mode


def validate_dense_operand(
    b, *, k_expected: int, context: str, name: str = "B",
    rows_label: str = "K", cols_label: str = "N",
    rows_reason: str = "the plan contracts over",
) -> None:
    """Shape/dtype validation of a dense operand with errors naming the
    caller's objects — BEFORE device placement or lowering sees the
    mismatch. ``name``/``rows_label`` retarget the messages at the
    two-dense-operand entry points (X, Y of SDDMM/fused).

    Works on tracers too (shape and dtype are static), so a wrong
    operand inside a jitted step fails just as legibly.
    """
    shape = tuple(getattr(b, "shape", np.shape(b)))
    if len(shape) != 2:
        raise ValueError(
            f"{context}: {name} must be 2-D [{rows_label}, {cols_label}]; "
            f"got shape {shape}. "
            f"Reshape a vector operand to ({rows_label}, 1).")
    if int(shape[0]) != int(k_expected):
        raise ValueError(
            f"{context}: {name} has {shape[0]} rows but {rows_reason} "
            f"{rows_label}={k_expected} (C = A @ B with A's shape fixed at "
            f"plan time); pass a [{k_expected}, {cols_label}] operand or "
            f"re-plan for the new A.")
    dtype = getattr(b, "dtype", None)  # tracers carry one; lists don't
    dtype = np.dtype(dtype if dtype is not None else np.asarray(b).dtype)
    if dtype.kind not in "fc":
        raise TypeError(
            f"{context}: {name} has dtype {dtype} but the kernels "
            f"accumulate in floating point; cast to float32 (or another "
            f"inexact dtype) before the call.")


def validate_sddmm_operands(x, y, *, m_expected: int, k_expected: int,
                            context: str) -> None:
    """X/Y validation for the SDDMM and fused entry points.

    X samples the pattern's ROW side (sharded like C) and Y its COLUMN
    side (sharded like B); their feature widths must agree since every
    stored nonzero contracts ``x_i · y_j``. Each error names the
    offending operand, pre-XLA, tracer-safe.
    """
    validate_dense_operand(x, k_expected=m_expected, context=context,
                           name="X", rows_label="M", cols_label="F",
                           rows_reason="the plan's row partition fixes")
    validate_dense_operand(y, k_expected=k_expected, context=context,
                           name="Y", rows_label="K", cols_label="F",
                           rows_reason="the plan's column partition fixes")
    fx = int(tuple(getattr(x, "shape", np.shape(x)))[1])
    fy = int(tuple(getattr(y, "shape", np.shape(y)))[1])
    if fx != fy:
        raise ValueError(
            f"{context}: X has F={fx} feature columns but Y has F={fy}; "
            f"SDDMM contracts x_i · y_j per stored nonzero, so the two "
            f"dense operands must share one feature width.")


def validate_sparse_values(a, *, context: str) -> None:
    """Finite-values validation of the sparse operand's nonzeros.

    Runs at plan/replan time — once per pattern generation, off the
    serving path — because a poisoned ``a.data`` otherwise spreads NaN
    into every served C that touches the bad nonzero.
    """
    data = np.asarray(a.data)
    bad = np.flatnonzero(~np.isfinite(data))
    if bad.size:
        i = int(bad[0])
        raise NumericalFault(
            f"{context}: sparse operand carries {bad.size} non-finite "
            f"nonzero value(s); first at data[{i}] = {data[i]!r} of "
            f"nnz={data.size}. Sanitize the operand (or set check=False "
            f"to plan anyway — every dependent C row will be poisoned).")


def validate_pattern(snapshot_new, snapshot_expected, *,
                     context: str) -> None:
    """Pattern-digest validation: the operand being attached must carry
    the exact sparsity pattern the plan was built for."""
    if snapshot_expected is None or snapshot_new is None:
        return
    if snapshot_new.fingerprint != snapshot_expected.fingerprint:
        raise ValueError(
            f"{context}: operand pattern digest "
            f"{snapshot_new.fingerprint[:12]} does not match the planned "
            f"pattern {snapshot_expected.fingerprint[:12]} (shape "
            f"{snapshot_new.shape} vs {snapshot_expected.shape}, nnz "
            f"{snapshot_new.nnz} vs {snapshot_expected.nnz}); use "
            f"SpmmSession.replan/maybe_replan for a drifted pattern "
            f"instead of attaching mismatched values.")


class FiniteProbe(NamedTuple):
    """What the sweep reads of one addressable piece of an output."""

    offset: int  # global row of the piece's first row
    found: Any   # int32 [row, col] of the first non-finite element; row -1 if none
    value: Any   # that element (or element [0, 0] when none), in the piece's dtype


def _sample_rows(n_rows: int, full: bool) -> Optional[np.ndarray]:
    """The rows the sweep reads of a piece with ``n_rows`` rows, or None
    for every row: all under ``"full"`` and for small pieces, otherwise
    the corner and strided rows."""
    if full or n_rows <= _SAMPLE_ROWS:
        return None
    return np.unique(np.linspace(0, n_rows - 1, _SAMPLE_ROWS, dtype=np.int64))


def _first_nonfinite(x, *, full: bool, vector_is_row: bool, xp):
    """``(found, value)`` of one piece: the first non-finite element of
    the rows the mode reads, in ``np.argwhere``'s row-major order, in the
    piece's 2-D view (leading dim = rows; a vector is one row when
    ``vector_is_row``). Written once for NumPy and for ``jax.numpy``."""
    if vector_is_row and x.ndim == 1:
        x = x[None, :]
    rows = _sample_rows(x.shape[0], full)
    sampled = x if rows is None else x[rows]
    sampled = sampled.reshape(sampled.shape[0], -1)
    bad = ~xp.isfinite(sampled)
    row_bad = bad.any(axis=1)
    r = xp.argmax(row_bad)
    col = xp.argmax(bad[r])
    local = r if rows is None else xp.asarray(rows)[r]
    found = xp.stack([xp.where(row_bad[r], local, -1), col]).astype(xp.int32)
    return found, sampled[r, col]


@functools.cache
def _device_probe():
    """The device half of the sweep, one jitted function: its compiled
    programs are keyed by the piece's shape, dtype and device and by the
    mode, and the rows come from the static shape while it traces. Built
    on first use, so that importing this module imports no JAX."""
    import jax
    import jax.numpy as jnp

    return jax.jit(functools.partial(_first_nonfinite, xp=jnp),
                   static_argnames=("full", "vector_is_row"))


def _pieces(c) -> Iterator[Tuple[int, Any]]:
    """(global_row_offset, piece) per addressable piece of C: each shard's
    own device array, or the host array itself."""
    if hasattr(c, "addressable_shards"):
        for shard in c.addressable_shards:
            rows = shard.index[0] if shard.index else slice(None)
            start = rows.start if getattr(rows, "start", None) else 0
            yield int(start), shard.data
    else:
        yield 0, np.asarray(c)


def _is_empty(shape: Tuple[int, ...], vector_is_row: bool) -> bool:
    if vector_is_row and len(shape) == 1:
        return shape[0] == 0
    return shape[0] == 0 or int(np.prod(shape[1:])) == 0


def probe_finite(c, *, mode: Any = "auto",
                 vector_is_row: bool = True) -> List[FiniteProbe]:
    """Launch the sweep over every addressable piece of ``c``.

    A device piece is reduced on its own device, so no byte of ``c``
    moves between chips or to the host; a host array is reduced on the
    host. Read the results with ``read_probes``.
    """
    full = mode in ("full", True)
    probes = []
    for offset, piece in _pieces(c):
        if _is_empty(tuple(piece.shape), vector_is_row):
            continue
        if isinstance(piece, np.ndarray):
            found, value = _first_nonfinite(piece, full=full,
                                            vector_is_row=vector_is_row,
                                            xp=np)
        else:
            found, value = _device_probe()(piece, full=full,
                                           vector_is_row=vector_is_row)
        probes.append(FiniteProbe(offset, found, value))
    return probes


def compile_probe(out, *, mode: Any = "auto") -> None:
    """Compile the device sweep for every addressable piece of an output
    described by ``out`` (a ``jax.ShapeDtypeStruct`` with its sharding),
    so that the first call it guards compiles nothing."""
    import jax
    from jax.sharding import SingleDeviceSharding

    shape = out.sharding.shard_shape(out.shape)
    if _is_empty(tuple(shape), True):
        return
    for device in out.sharding.addressable_devices:
        piece = jax.ShapeDtypeStruct(shape, out.dtype,
                                     sharding=SingleDeviceSharding(device))
        _device_probe().lower(piece, full=mode in ("full", True),
                              vector_is_row=True).compile()


def read_probes(probes: List[FiniteProbe]) -> Tuple[List[FiniteProbe], int]:
    """Wait for the probes and read them to the host in one
    ``jax.device_get``; returns them with the bytes read from devices."""
    import jax

    read = sum(a.nbytes for p in probes for a in (p.found, p.value)
               if isinstance(a, jax.Array))
    return jax.device_get(probes), read


def _mode_name(mode: Any) -> str:
    return "full" if mode in ("full", True) else "auto"


def raise_nonfinite(probes: List[FiniteProbe], *, mode: Any = "auto",
                    context: str = "DistSpmm",
                    call_index: Optional[int] = None) -> None:
    """The host half of the C sweep: raise ``NumericalFault`` naming the
    first non-finite element (global row, col) that the read probes
    found, piece by piece."""
    for p in probes:
        row, col = (int(v) for v in p.found)
        if row < 0:
            continue
        val = np.asarray(p.value)[()]
        at = f" on call #{call_index}" if call_index is not None else ""
        raise NumericalFault(
            f"{context}: non-finite C[{p.offset + row}, {col}] = {val!r}{at} "
            f"(check={_mode_name(mode)} "
            f"isfinite sweep). The producer is upstream — a poisoned "
            f"operand value or a broken backend kernel; set check=False "
            f"to serve unchecked.")


def sampled_finite_check(c, *, mode: Any = "auto",
                         context: str = "DistSpmm",
                         call_index: Optional[int] = None) -> int:
    """The post-call C sweep: raise ``NumericalFault`` naming the first
    non-finite element (global row, col) found in the sampled rows, or
    return the bytes the sweep read back from devices.

    ``"auto"`` samples the corner and strided rows of every addressable
    block (full coverage when a block is small); ``"full"`` checks every
    row. Sampling trades exhaustiveness for serving-path cost — a
    poisoned operand row poisons every C column it touches, so row
    sampling catches the systematic producers (bad operand values, a
    broken backend kernel) cheaply.
    """
    probes, read = read_probes(probe_finite(c, mode=mode))
    raise_nonfinite(probes, mode=mode, context=context, call_index=call_index)
    return read


def sampled_finite_check_tree(values, *, mode: Any = "auto",
                              context: str = "DistSpmm",
                              call_index: Optional[int] = None) -> int:
    """The post-call sweep over a PYTREE of outputs (SDDMM's sampled
    values: one leaf per piece, in the backend's native layout).

    Each leaf runs the same row-sampled sweep as C; leaves are viewed as
    2-D (leading dim = rows) so the BSR block layout sweeps too. The
    fault message names the leaf's tree path instead of C's row/col.
    Every leaf's probes are read back together; returns the bytes read.
    """
    import jax

    labels, probes = [], []
    for path, leaf in jax.tree_util.tree_leaves_with_path(values):
        for p in probe_finite(leaf, mode=mode, vector_is_row=False):
            labels.append(jax.tree_util.keystr(path))
            probes.append(p)
    probes, read = read_probes(probes)
    for label, p in zip(labels, probes):
        if int(p.found[0]) < 0:
            continue
        val = np.asarray(p.value)[()]
        at = f" on call #{call_index}" if call_index is not None else ""
        raise NumericalFault(
            f"{context}: non-finite sampled value {val!r} in output "
            f"leaf {label!r}{at} "
            f"(check={_mode_name(mode)} "
            f"isfinite sweep). The producer is upstream — a poisoned "
            f"X/Y operand value or a broken backend kernel; set "
            f"check=False to serve unchecked.")
    return read
