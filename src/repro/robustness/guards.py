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
              shard — O(sample) host work, not O(m·n)).
  ``"full"`` / ``True``  the same, but the C sweep checks every element.

A failed C sweep raises ``NumericalFault`` naming the first bad element
and the handle call that produced it; ``SpmmWaveServer`` catches it like
any wave failure (retry, then surface), so its message also ends up
naming the first bad wave.
"""
from __future__ import annotations

from typing import Any, Iterator, Optional, Tuple

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
    "start_host_copy",
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


def start_host_copy(c) -> None:
    """Start copying each addressable piece of C to the host: the pieces
    that the sweep reads (``addressable_shards`` hands back the same
    arrays each time), so that the copy follows C on the device instead
    of waiting for the host to see C ready."""
    for shard in getattr(c, "addressable_shards", ()):
        shard.data.copy_to_host_async()


def _blocks(c) -> Iterator[Tuple[int, np.ndarray]]:
    """(global_row_offset, host_block) per addressable piece of C."""
    if hasattr(c, "addressable_shards"):
        for shard in c.addressable_shards:
            rows = shard.index[0] if shard.index else slice(None)
            start = rows.start if getattr(rows, "start", None) else 0
            yield int(start), np.asarray(shard.data)
    else:
        yield 0, np.asarray(c)


def sampled_finite_check(c, *, mode: Any = "auto",
                         context: str = "DistSpmm",
                         call_index: Optional[int] = None) -> int:
    """The post-call C sweep: raise ``NumericalFault`` naming the first
    non-finite element (global row, col) found in the sampled rows, or
    return the bytes of C the sweep copied to the host.

    ``"auto"`` samples the corner and strided rows of every addressable
    block (full coverage when a block is small); ``"full"`` checks every
    row. Sampling trades exhaustiveness for serving-path cost — a
    poisoned operand row poisons every C column it touches, so row
    sampling catches the systematic producers (bad operand values, a
    broken backend kernel) cheaply.
    """
    host_bytes = 0
    for offset, block in _blocks(c):
        host_bytes += block.nbytes
        if block.ndim == 1:
            block = block[None, :]
        n_rows = block.shape[0]
        if n_rows == 0:
            continue
        if mode in ("full", True) or n_rows <= _SAMPLE_ROWS:
            rows = np.arange(n_rows)
        else:
            rows = np.unique(np.linspace(0, n_rows - 1, _SAMPLE_ROWS,
                                         dtype=np.int64))
        sampled = block[rows]
        finite = np.isfinite(sampled)
        if finite.all():
            continue
        where = np.argwhere(~finite)[0]
        r = int(offset + rows[int(where[0])])
        col = int(where[1]) if sampled.ndim > 1 else 0
        val = sampled[tuple(where)]
        at = f" on call #{call_index}" if call_index is not None else ""
        raise NumericalFault(
            f"{context}: non-finite C[{r}, {col}] = {val!r}{at} "
            f"(check={'full' if mode in ('full', True) else 'auto'} "
            f"isfinite sweep). The producer is upstream — a poisoned "
            f"operand value or a broken backend kernel; set check=False "
            f"to serve unchecked.")
    return host_bytes


def sampled_finite_check_tree(values, *, mode: Any = "auto",
                              context: str = "DistSpmm",
                              call_index: Optional[int] = None) -> int:
    """The post-call sweep over a PYTREE of outputs (SDDMM's sampled
    values: one leaf per piece, in the backend's native layout).

    Each leaf runs the same row-sampled sweep as C; leaves are viewed as
    2-D (leading dim = rows) so the BSR block layout sweeps too. The
    fault message names the leaf's tree path instead of C's row/col.
    Returns the bytes the sweep copied to the host.
    """
    import jax

    host_bytes = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(values):
        label = jax.tree_util.keystr(path)
        for _, block in _blocks(leaf):
            host_bytes += block.nbytes
            flat = np.asarray(block).reshape(block.shape[0], -1)
            if flat.shape[0] == 0 or flat.shape[1] == 0:
                continue
            if mode in ("full", True) or flat.shape[0] <= _SAMPLE_ROWS:
                rows = np.arange(flat.shape[0])
            else:
                rows = np.unique(np.linspace(0, flat.shape[0] - 1,
                                             _SAMPLE_ROWS, dtype=np.int64))
            sampled = flat[rows]
            finite = np.isfinite(sampled)
            if finite.all():
                continue
            where = np.argwhere(~finite)[0]
            val = sampled[tuple(where)]
            at = f" on call #{call_index}" if call_index is not None else ""
            raise NumericalFault(
                f"{context}: non-finite sampled value {val!r} in output "
                f"leaf {label!r}{at} "
                f"(check={'full' if mode in ('full', True) else 'auto'} "
                f"isfinite sweep). The producer is upstream — a poisoned "
                f"X/Y operand value or a broken backend kernel; set "
                f"check=False to serve unchecked.")
    return host_bytes
