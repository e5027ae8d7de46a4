"""Pluggable local-compute backends for the distributed SpMM executors.

SHIRO's speedups come from pairing a sparsity-aware communication schedule
with the fastest available *local* SpMM. This module is the seam between
the two: the executors (core.dist_spmm) fix the collectives, and a
``LocalSpmmBackend`` fixes how each padded sparse piece (diagonal block,
column-covered part, row-covered part) is multiplied against its dense
operand on-device.

A backend owns both sides of the seam:

* ``prepare(csrs)`` — host side, once per plan: convert the planner's
  per-process CSR pieces into stacked device arrays in the backend's
  native layout (leading axis = process).
* ``compute(piece, b, m_out)`` — device side, called INSIDE the shard_map
  body on a single process's piece (leading axis already stripped).

Swapping backends changes local FLOPs only — the communication schedule
(all_to_all / psum_scatter buffers) never sees the piece layout, so the
lowered collectives are bit-identical across backends.

Built-ins:

* ``CooBackend`` — padded COO plus a degree-bucketed row layout built at
  prepare time. The product folds each row's nonzeros into that row, in
  COO order, one slot after another, and gathers the bucket outputs back
  into row order: no scatter-add into C and no per-call sort of row ids.
  The per-row addition chain is the COO scatter-add's, so C, staged or
  per round, matches ``coo_spmm_local`` bit for bit on the CPU. Its
  derivative is the COO expression (``kernels.ops.
  coo_accumulate_rows_op``). It tolerates arbitrary shapes; the portable
  default.
* ``BsrBackend`` — ELL block layout feeding the Pallas MXU kernel
  (kernels.bsr_spmm). ``interpret=None`` auto-selects interpret mode off
  TPU; ``impl="ref"`` forces the pure-jnp oracle (kernels.ref).

Third backends register via ``register_backend`` (see ROADMAP.md
"Backends & JAX compatibility").
"""
from __future__ import annotations

import dataclasses
from typing import (
    ClassVar, Dict, List, Protocol, Sequence, Tuple, Union, runtime_checkable,
)

import jax
import jax.numpy as jnp
import numpy as np

from .sparse import CSRMatrix, ell_from_csr

__all__ = [
    "LocalSpmmBackend",
    "CooBackend",
    "BsrBackend",
    "coo_spmm_local",
    "get_backend",
    "register_backend",
    "available_backends",
    "backend_prepare_segments",
    "backend_compute_segment",
    "backend_sddmm",
    "backend_with_values",
    "coo_sddmm_local",
    "RowFold",
    "fold_counts",
]

Piece = Dict[str, jax.Array]


@runtime_checkable
class LocalSpmmBackend(Protocol):
    """Local sparse-times-dense substrate used inside the executors.

    Beyond ``prepare``/``compute``, a backend MAY implement the
    round-pipelined pair ``prepare_segments``/``compute_segment`` (see
    ``backend_prepare_segments`` / ``backend_compute_segment`` for the
    contract and the generic fallbacks the executors use otherwise), and
    the SDDMM pair ``sddmm``/``with_values`` that the sibling kernel
    family (core.dist_sddmm) requires — see ``backend_sddmm`` /
    ``backend_with_values``.
    """

    name: str

    def prepare(self, csrs: List[CSRMatrix]) -> Piece:
        """Stack per-process CSR pieces into device arrays [P, ...]."""

    def compute(self, piece: Piece, b: jax.Array, m_out: int) -> jax.Array:
        """C[m_out, N] = piece @ b for one process's (stripped) piece."""


# ---------------------------------------------------------------------------
# per-round segment compute (overlapped executors)
# ---------------------------------------------------------------------------
#
# The overlapped executors (core.dist_spmm, overlap=True) consume a piece
# one communication round at a time. The contract is CUMULATIVE-PREFIX:
#
# * ``prepare_segments(csrs, cuts)`` — host side. ``cuts`` are ascending
#   column cut points over the piece's flat receive space (one per round,
#   the last equal to the covered width). Segment ``i`` owns the nonzeros
#   the backend assigns to rounds ``(prev_cut, cuts[i]]`` — column indices
#   stay ABSOLUTE, so a backend may move a nonzero to a LATER segment
#   (e.g. a BSR block straddling a cut waits for the next round) but
#   never to an earlier one.
# * ``compute_segment(piece, b_prefix, acc)`` — device side.
#   ``b_prefix`` is the concatenation of every received segment so far
#   (rows ``[0, cuts[i])`` of the staged receive space), and the return
#   value is ``acc`` plus this segment's contributions.
#
# Accumulating segment-by-segment in ascending-cut order therefore
# replays the staged compute's per-element addition chain exactly: the
# fold over segments inserts only exact identity terms (fresh zero
# accumulators), which is what makes overlapped and staged execution
# bit-identical rather than merely allclose.


def _cut_cols(csrs: List[CSRMatrix], lo: int, hi: int) -> List[CSRMatrix]:
    """Keep only nonzeros with column in [lo, hi); shape/indices unchanged."""
    return [c.select_nonzeros((c.indices >= lo) & (c.indices < hi))
            for c in csrs]


def backend_prepare_segments(be: "LocalSpmmBackend", csrs: List[CSRMatrix],
                             cuts: Sequence[int]) -> List[Piece]:
    """Per-round piece layouts (backend override or the generic cut)."""
    fn = getattr(be, "prepare_segments", None)
    if fn is not None:
        return fn(csrs, cuts)
    out, lo = [], 0
    for hi in cuts:
        out.append(be.prepare(_cut_cols(csrs, lo, hi)))
        lo = hi
    return out


def backend_compute_segment(be: "LocalSpmmBackend", piece: Piece,
                            b_prefix: jax.Array, acc: jax.Array) -> jax.Array:
    """acc + (segment piece @ b_prefix) — override or generic fallback."""
    fn = getattr(be, "compute_segment", None)
    if fn is not None:
        return fn(piece, b_prefix, acc)
    return acc + be.compute(piece, b_prefix, acc.shape[0])


# ---------------------------------------------------------------------------
# SDDMM contract (core.dist_sddmm executors)
# ---------------------------------------------------------------------------
#
# The SDDMM kernel family reuses a piece's native layout with the
# dataflow reversed: instead of folding stored values against dense ROWS
# of B, every stored nonzero (i, j) SAMPLES the dot product x_i · y_j and
# scales it by its stored value. Two methods close the loop:
#
# * ``sddmm(piece, x, y)`` — device side, inside the shard_map body.
#   ``x`` indexes the piece's ROW space and ``y`` its COLUMN space (the
#   executors hand each piece exactly the buffers its index spaces refer
#   to — local rows for the diagonal, gathered rows for the covered
#   parts). Returns the sampled values in the backend's NATIVE value
#   layout (the same shape ``prepare`` stored them in), padding slots
#   zero because their stored values are zero.
# * ``with_values(piece, vals)`` — swap a piece's stored values for
#   ``vals`` (a ``sddmm`` result), leaving the index structure untouched.
#   This is what lets FusedMM chain SDDMM→SpMM without re-laying out
#   anything: the sampled values drop straight into the SpMM kernels.
#   Shape-agnostic over the leading process axis, so it works both on
#   stripped pieces inside shard_map and on stacked [P, ...] arrays.


def backend_sddmm(be: "LocalSpmmBackend", piece: Piece, x: jax.Array,
                  y: jax.Array) -> Piece:
    """Sampled values for one (stripped) piece — backend method required."""
    fn = getattr(be, "sddmm", None)
    if fn is None:
        raise NotImplementedError(
            f"backend {be.name!r} implements no sddmm(piece, x, y); the "
            f"kernel='sddmm'/'fused' family needs it (see CooBackend / "
            f"BsrBackend for the contract).")
    return fn(piece, x, y)


def backend_with_values(be: "LocalSpmmBackend", piece: Piece,
                        vals) -> Piece:
    """Piece with stored values swapped for ``vals`` — method required."""
    fn = getattr(be, "with_values", None)
    if fn is None:
        raise NotImplementedError(
            f"backend {be.name!r} implements no with_values(piece, vals); "
            f"the kernel='fused' executor needs it to feed sampled values "
            f"back into the SpMM phase.")
    return fn(piece, vals)


# ---------------------------------------------------------------------------
# COO backend (portable default)
# ---------------------------------------------------------------------------


def coo_spmm_local(row: jax.Array, col: jax.Array, val: jax.Array,
                   b: jax.Array, m_out: int) -> jax.Array:
    """C[m_out, N] = scatter-add_{e} val[e] * b[col[e]] into row[e].

    Padded entries carry val == 0 so they contribute nothing.
    """
    gathered = b[col] * val[:, None]
    return jnp.zeros((m_out, b.shape[1]), b.dtype).at[row].add(gathered)


def coo_sddmm_local(row: jax.Array, col: jax.Array, val: jax.Array,
                    x: jax.Array, y: jax.Array) -> jax.Array:
    """vals[e] = val[e] * (x[row[e]] · y[col[e]]) per stored nonzero.

    Padded entries carry val == 0 (and row == col == 0, which gather
    real but ignored rows), so they sample to exactly zero.
    """
    return val * (x[row] * y[col]).sum(axis=-1)


def _stack_coo(csrs: List[CSRMatrix]) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack per-process CSR pieces into padded COO [P, nnz_max] arrays."""
    coos = [c.to_coo() for c in csrs]
    nnz = max((c.nnz for c in coos), default=0)
    nnz = max(nnz, 1)
    P_ = len(csrs)
    row = np.zeros((P_, nnz), np.int32)
    col = np.zeros((P_, nnz), np.int32)
    val = np.zeros((P_, nnz), np.float32)
    for i, c in enumerate(coos):
        row[i, : c.nnz] = c.row
        col[i, : c.nnz] = c.col
        val[i, : c.nnz] = c.val
    return row, col, val


# The row fold's bucket widths: one per degree up to _FOLD_EXACT, then
# four steps an octave up to 32 and two (1.5x, 2x) an octave above. A
# piece keeps only the widths its rows' degrees occupy.
_FOLD_EXACT = 16


def _fold_ladder(max_deg: int) -> np.ndarray:
    """Every bucket width a piece of row degree <= ``max_deg`` may use."""
    out = list(range(1, _FOLD_EXACT + 1))
    while out[-1] < max_deg:
        base = out[-1]  # a power of two
        steps = (1.25, 1.5, 1.75, 2) if base < 32 else (1.5, 2)
        out.extend(int(base * s) for s in steps)
    return np.asarray(out, np.int64)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RowFold:
    """A piece's degree-bucketed row layout (``coo_accumulate_rows_op``).

    Buckets lie one after another in each array; ``buckets`` gives each
    one's width ``w`` and row count ``R``. Every array leads with the
    piece's process axes, if any:

    * ``perm`` [m]: each row of C's bucket output row, in the buckets'
      concatenated outputs, or -1 for a row with no nonzeros;
    * ``rows`` [sum R]: each bucket's row ids;
    * ``src``, ``col``, ``val`` [sum w·R]: per bucket, slot-major ([w, R]
      flattened), for slot ``k`` of each row the COO index of the
      nonzero it folds, in the row's COO order, that nonzero's column and
      its value;
    * ``nnz`` []: the stored nonzeros.

    Six flat arrays, however many buckets: each is an argument of every
    executable that runs the piece, and each argument costs host time at
    dispatch.
    """

    perm: jax.Array
    rows: jax.Array
    src: jax.Array
    col: jax.Array
    val: jax.Array
    nnz: jax.Array
    buckets: Tuple[Tuple[int, int], ...] = dataclasses.field(
        metadata=dict(static=True), default=())

    def with_values(self, vals: jax.Array) -> "RowFold":
        """The layout with each slot's value read anew from ``vals``
        [..., nnz]; padding slots read 0."""
        pad = jnp.zeros(vals.shape[:-1] + (1,), vals.dtype)
        ext = jnp.concatenate([vals, pad], axis=-1)
        return dataclasses.replace(
            self, val=jnp.take_along_axis(ext, self.src, axis=-1))


def _fold_layout(csrs: List[CSRMatrix], val: np.ndarray) -> RowFold:
    """The ``RowFold`` of stacked COO pieces; ``val`` is their stacked
    values, [P, nnz_max].

    Rows with nonzeros go to buckets of ladder widths. A padding slot
    points at index ``nnz_max``, one past the stored values, holds the
    value 0 and repeats its row's last column, so it adds an exact zero
    and reads no row of B the real slots do not.

    Buckets share one row count across processes. The counts are the
    fewest that hold every process's rows when each row takes the
    smallest width that holds it or, where its own bucket is full, a
    wider one: bucket ``j`` holds as many rows as the process with the
    most rows of width ``>= ladder[j]`` has beyond the wider buckets'
    counts. With one process each row takes its smallest width. A bucket
    of 64 rows or more that the device folds unrolled rounds its rows up
    to the (8, 128) tile's 8 sublanes, so that its terms, gathered flat,
    are a [w, R, N] view with no relayout. Padding rows are never
    gathered back.
    """
    from ..kernels.ops import FOLD_UNROLL

    coos = [c.to_coo() for c in csrs]
    P_, pad = val.shape
    m = max((c.shape[0] for c in csrs), default=0)
    degs = [np.bincount(c.row, minlength=m) for c in coos]
    ladder = _fold_ladder(max((int(d.max(initial=0)) for d in degs),
                              default=0))
    rungs = [np.where(d > 0, np.searchsorted(ladder, d), -1) for d in degs]
    counts = np.stack([np.bincount(r[r >= 0], minlength=ladder.size)
                       for r in rungs])
    # rows that need width >= ladder[j], most over processes
    need = counts[:, ::-1].cumsum(axis=1)[:, ::-1].max(axis=0)
    sizes = need - np.append(need[1:], 0)
    sizes = np.where((ladder <= FOLD_UNROLL) & (sizes >= 64),
                     -(-sizes // 8) * 8, sizes)
    buckets = tuple((int(ladder[j]), int(n)) for j, n in enumerate(sizes)
                    if n)
    row_off = np.cumsum([0] + [n for _, n in buckets])
    slot_off = np.cumsum([0] + [w * n for w, n in buckets])
    perm = np.full((P_, m), -1, np.int32)
    rows_a = np.zeros((P_, row_off[-1]), np.int32)
    src_a = np.full((P_, slot_off[-1]), pad, np.int32)
    col_a = np.zeros((P_, slot_off[-1]), np.int32)
    for p, c in enumerate(coos):
        # widest rows first into the widest buckets, then down the ladder
        order = np.argsort(c.row, kind="stable")
        start = np.cumsum(degs[p]) - degs[p]
        filled = np.nonzero(rungs[p] >= 0)[0]
        filled = filled[np.argsort(-rungs[p][filled], kind="stable")]
        hi = 0
        for i in reversed(range(len(buckets))):
            w, n = buckets[i]
            rows = np.sort(filled[hi:hi + n])
            hi += rows.size
            d = degs[p][rows]
            k = np.arange(w)[:, None]
            e = order[start[rows] + np.minimum(k, d - 1)]  # [w, rows]
            src = np.full((w, n), pad, np.int32)
            col = np.zeros((w, n), np.int32)
            src[:, :rows.size] = np.where(k < d, e, pad)
            col[:, :rows.size] = c.col[e]
            rows_a[p, row_off[i]:row_off[i] + rows.size] = rows
            src_a[p, slot_off[i]:slot_off[i + 1]] = src.ravel()
            col_a[p, slot_off[i]:slot_off[i + 1]] = col.ravel()
            perm[p, rows] = row_off[i] + np.arange(rows.size)
    val_ext = np.concatenate([val, np.zeros((P_, 1), val.dtype)], axis=1)
    return RowFold(perm=perm, rows=rows_a, src=src_a, col=col_a,
                   val=np.take_along_axis(val_ext, src_a, axis=1),
                   nnz=np.array([c.nnz for c in coos], np.int32),
                   buckets=buckets)


def fold_counts(piece: Piece) -> Tuple[int, int, int]:
    """(stored nonzeros, those the row fold reduces, slots it folds) of a
    prepared piece. A piece without a fold layout counts its nonzero
    values."""
    fold = piece.get("fold")
    if fold is not None:
        nnz = int(np.asarray(fold.nnz).sum())
        return nnz, nnz, int(fold.src.size)
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(piece)]
    return sum(int(np.count_nonzero(x)) for x in leaves
               if np.issubdtype(x.dtype, np.floating)), 0, 0


@dataclasses.dataclass(frozen=True)
class CooBackend:
    """Padded COO plus a degree-bucketed row layout; the product folds
    each row's nonzeros in order (``coo_accumulate_rows_op``)."""

    name: ClassVar[str] = "coo"

    def prepare(self, csrs: List[CSRMatrix]) -> Piece:
        row, col, val = _stack_coo(csrs)
        fold = _fold_layout(csrs, val)
        return {"row": jnp.asarray(row), "col": jnp.asarray(col),
                "val": jnp.asarray(val),
                "fold": jax.tree_util.tree_map(jnp.asarray, fold)}

    def compute(self, piece: Piece, b: jax.Array, m_out: int) -> jax.Array:
        from ..kernels.ops import coo_accumulate_rows_op

        return coo_accumulate_rows_op(
            jnp.zeros((m_out, b.shape[1]), b.dtype), piece["fold"],
            piece["row"], piece["col"], piece["val"], b, True)

    def compute_segment(self, piece: Piece, b_prefix: jax.Array,
                        acc: jax.Array) -> jax.Array:
        # the row fold resumed from the running accumulator: the same
        # per-row addition chain the staged compute runs from zeros
        from ..kernels.ops import coo_accumulate_rows_op

        return coo_accumulate_rows_op(acc, piece["fold"], piece["row"],
                                      piece["col"], piece["val"], b_prefix)

    def sddmm(self, piece: Piece, x: jax.Array, y: jax.Array) -> jax.Array:
        return coo_sddmm_local(piece["row"], piece["col"], piece["val"],
                               x, y)

    def with_values(self, piece: Piece, vals: jax.Array) -> Piece:
        return dict(piece, val=vals, fold=piece["fold"].with_values(vals))


# ---------------------------------------------------------------------------
# BSR/ELL backend (MXU-ready Pallas kernel)
# ---------------------------------------------------------------------------


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


@dataclasses.dataclass(frozen=True)
class BsrBackend:
    """ELL block layout feeding the Pallas BSR kernel.

    ``block``: (bm, bk) dense-block shape emitted by the planner layer —
    128×128 saturates the MXU on real TPUs; small tests shrink it.
    ``bn``: kernel output tile width; N is zero-padded up to a multiple.
    ``interpret``: None → auto (Pallas interpret mode everywhere but TPU).
    ``impl``: "pallas" | "ref" — "ref" routes through the pure-jnp oracle
    (kernels.ref.bsr_spmm_ref) instead of pallas_call entirely.
    """

    name: ClassVar[str] = "bsr"

    block: Tuple[int, int] = (8, 8)
    bn: int = 128
    interpret: Union[bool, None] = None
    impl: str = "pallas"

    def resolve_interpret(self) -> bool:
        """``interpret`` with None resolved: interpret mode off a TPU."""
        if self.interpret is None:
            return jax.default_backend() != "tpu"
        return bool(self.interpret)

    def prepare(self, csrs: List[CSRMatrix]) -> Piece:
        per = [ell_from_csr(c, self.block) for c in csrs]
        t = max(bc.shape[1] for bc, _ in per)
        bm, bk = self.block
        P_ = len(per)
        mb = per[0][0].shape[0]
        cols = np.full((P_, mb, t), -1, np.int32)
        blocks = np.zeros((P_, mb, t, bm, bk), np.float32)
        for i, (bc, blk) in enumerate(per):
            cols[i, :, : bc.shape[1]] = bc
            blocks[i, :, : bc.shape[1]] = blk
        return {"block_cols": jnp.asarray(cols), "blocks": jnp.asarray(blocks)}

    def compute(self, piece: Piece, b: jax.Array, m_out: int) -> jax.Array:
        cols, blocks = piece["block_cols"], piece["blocks"]
        _, _, bm, bk = blocks.shape
        k, n = b.shape
        kb = _round_up(k, bk) // bk
        if self.impl == "ref":
            from ..kernels.ref import bsr_spmm_ref

            # the oracle has no tile-width requirement: pad K only
            out = bsr_spmm_ref(cols, blocks,
                               jnp.pad(b, ((0, kb * bk - k), (0, 0))))
        else:
            from ..kernels.bsr_spmm import bsr_spmm_pallas

            n_pad = _round_up(n, self.bn)
            b_p = jnp.pad(b, ((0, kb * bk - k), (0, n_pad - n)))
            out = bsr_spmm_pallas(cols, blocks, b_p, bn=self.bn,
                                  interpret=self.resolve_interpret())
        return out[:m_out, :n].astype(b.dtype)

    def prepare_segments(self, csrs: List[CSRMatrix],
                         cuts: Sequence[int]) -> List[Piece]:
        """Block-aligned rounds: interior cuts floor to the bk grid.

        A (bm × bk) block straddling a cut would mix two rounds'
        received columns inside one MXU dot, so it is deferred to the
        first round whose prefix covers it whole — the cumulative-prefix
        contract allows exactly this. Block-column ids stay absolute, so
        every segment's blocks index the same K grid the staged kernel
        uses and the per-element accumulation chains coincide.
        """
        bk = self.block[1]
        out, lo = [], 0
        for i, hi in enumerate(cuts):
            hi_b = hi if i == len(cuts) - 1 else (hi // bk) * bk
            hi_b = max(hi_b, lo)
            out.append(self.prepare(_cut_cols(csrs, lo, hi_b)))
            lo = hi_b
        return out

    def compute_segment(self, piece: Piece, b_prefix: jax.Array,
                        acc: jax.Array) -> jax.Array:
        """Resume the staged kernel's t-step accumulation chain.

        The staged kernel folds one stored block per t step into the
        output tile; summing a whole segment before adding it to ``acc``
        would regroup that chain (``acc + (c₁ + c₂)`` vs
        ``(acc + c₁) + c₂``) and drift by an ulp. The accumulator-operand
        kernel (``bsr_spmm_acc_pallas``) seeds its output tile with
        ``acc`` and folds the segment's slots in ascending t order — the
        exact chain, in ONE kernel launch whose accumulator buffer is
        input/output-aliased instead of freshly allocated per slot
        (``impl="ref"`` replays the chain slot-by-slot through the jnp
        oracle and is only allclose against the kernel paths).
        """
        cols, blocks = piece["block_cols"], piece["blocks"]
        if self.impl == "ref":
            for t in range(cols.shape[1]):
                step = {"block_cols": cols[:, t:t + 1],
                        "blocks": blocks[:, t:t + 1]}
                acc = acc + self.compute(step, b_prefix, acc.shape[0])
            return acc
        from ..kernels.bsr_spmm import bsr_spmm_acc_pallas

        mb, _, bm, bk = blocks.shape
        k, n = b_prefix.shape
        kb = _round_up(k, bk) // bk
        n_pad = _round_up(n, self.bn)
        b_p = jnp.pad(b_prefix, ((0, kb * bk - k), (0, n_pad - n)))
        m_out = acc.shape[0]
        acc_p = jnp.pad(acc.astype(jnp.float32),
                        ((0, mb * bm - m_out), (0, n_pad - n)))
        out = bsr_spmm_acc_pallas(cols, blocks, b_p, acc_p, bn=self.bn,
                                  interpret=self.resolve_interpret())
        return out[:m_out, :n].astype(b_prefix.dtype)

    def sddmm(self, piece: Piece, x: jax.Array, y: jax.Array) -> jax.Array:
        """Sampled [mb, t, bm, bk] block values = blocks ⊙ (X · Yᵀ).

        X/Y row counts are padded up to the block grid and the contracted
        feature width to a lane multiple — zero feature columns add
        nothing to the dots, zero rows land only on padding slots.
        """
        from ..kernels.sddmm import bsr_sddmm_op

        cols, blocks = piece["block_cols"], piece["blocks"]
        mb, _, bm, bk = blocks.shape
        kb = max(_round_up(y.shape[0], bk) // bk, 1)
        f = x.shape[1]
        f_pad = _round_up(max(f, 1), self.bn)
        x3 = jnp.pad(x, ((0, mb * bm - x.shape[0]), (0, f_pad - f)))
        x3 = x3.reshape(mb, bm, f_pad)
        y3 = jnp.pad(y, ((0, kb * bk - y.shape[0]), (0, f_pad - f)))
        y3 = y3.reshape(kb, bk, f_pad)
        return bsr_sddmm_op(cols, blocks, x3, y3, impl=self.impl,
                            interpret=self.resolve_interpret()).astype(x.dtype)

    def with_values(self, piece: Piece, vals: jax.Array) -> Piece:
        return dict(piece, blocks=vals)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_BACKENDS: Dict[str, LocalSpmmBackend] = {
    CooBackend.name: CooBackend(),
    BsrBackend.name: BsrBackend(),
}


def register_backend(backend: LocalSpmmBackend) -> None:
    """Install (or override) the default instance used for ``backend.name``."""
    _BACKENDS[backend.name] = backend


def available_backends() -> Tuple[str, ...]:
    return tuple(_BACKENDS)


def get_backend(spec: Union[str, LocalSpmmBackend]) -> LocalSpmmBackend:
    """Resolve a backend name or pass an instance through."""
    if isinstance(spec, str):
        try:
            return _BACKENDS[spec]
        except KeyError:
            raise ValueError(
                f"unknown backend {spec!r}; available: {available_backends()}"
            ) from None
    return spec
