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

* ``CooBackend`` — padded COO gather + segment scatter-add. XLA fuses it
  well on CPU and it tolerates arbitrary shapes; the portable default.
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


@dataclasses.dataclass(frozen=True)
class CooBackend:
    """Padded-COO gather + segment scatter-add (today's executor compute)."""

    name: ClassVar[str] = "coo"

    def prepare(self, csrs: List[CSRMatrix]) -> Piece:
        row, col, val = _stack_coo(csrs)
        return {"row": jnp.asarray(row), "col": jnp.asarray(col),
                "val": jnp.asarray(val)}

    def compute(self, piece: Piece, b: jax.Array, m_out: int) -> jax.Array:
        return coo_spmm_local(piece["row"], piece["col"], piece["val"],
                              b, m_out)

    def compute_segment(self, piece: Piece, b_prefix: jax.Array,
                        acc: jax.Array) -> jax.Array:
        # scatter straight into the running accumulator — the same
        # gather/scatter-add chain the staged compute runs, resumed
        from ..kernels.ops import coo_accumulate_rows_op

        return coo_accumulate_rows_op(acc, piece["row"], piece["col"],
                                      piece["val"], b_prefix)

    def sddmm(self, piece: Piece, x: jax.Array, y: jax.Array) -> jax.Array:
        return coo_sddmm_local(piece["row"], piece["col"], piece["val"],
                               x, y)

    def with_values(self, piece: Piece, vals: jax.Array) -> Piece:
        return dict(piece, val=vals)


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
