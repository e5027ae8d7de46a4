"""Public jit'd wrappers for the Pallas kernels.

``*_op`` functions dispatch per platform: the Pallas TPU kernel on TPU
backends, interpret-mode Pallas when ``REPRO_PALLAS_INTERPRET=1`` (CI /
CPU validation), and the pure-jnp oracle otherwise. All three paths are
numerically interchangeable (tests assert so), which keeps the distributed
executors platform-portable.

The executor-path ops (``gather_rows_op`` / ``scatter_add_rows_exec_op``)
carry ``custom_jvp`` rules whose tangents run through the jnp oracles:
``pallas_call`` has no JVP, but both ops are linear in their float
operands, so training (e.g. the GCN example differentiating through
``flat_spmm``) works on every kernel backend — forward stays on the
selected kernel, derivatives take the oracle path. The coo backend's row
fold (``coo_accumulate_rows_op``) does the same with the COO expression
as its tangent.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.custom_derivatives import SymbolicZero

from . import ref as _ref
from .bsr_spmm import bsr_spmm_acc_pallas, bsr_spmm_pallas
from .gather_rows import gather_rows_pallas
from .scatter_add_rows import prepare_sorted_scatter, scatter_add_rows_sorted_pallas

__all__ = [
    "kernel_backend",
    "bsr_spmm_op",
    "bsr_spmm_acc_op",
    "gather_rows_op",
    "scatter_add_rows_op",
    "pack_rows_op",
    "scatter_add_rows_exec_op",
    "coo_accumulate_rows_op",
    "prepare_sorted_scatter",
]


def kernel_backend() -> str:
    """'pallas' on TPU, 'interpret' if forced via env, else 'ref'."""
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return "interpret"
    if jax.default_backend() == "tpu":
        return "pallas"
    return "ref"


def bsr_spmm_op(block_cols: jax.Array, blocks: jax.Array, b: jax.Array,
                *, bn: int = 128) -> jax.Array:
    be = kernel_backend()
    if be == "pallas":
        return bsr_spmm_pallas(block_cols, blocks, b, bn=min(bn, b.shape[1]))
    if be == "interpret":
        return bsr_spmm_pallas(block_cols, blocks, b,
                               bn=min(bn, b.shape[1]), interpret=True)
    return _ref.bsr_spmm_ref(block_cols, blocks, b)


def bsr_spmm_acc_op(block_cols: jax.Array, blocks: jax.Array, b: jax.Array,
                    acc: jax.Array, *, bn: int = 128) -> jax.Array:
    """``acc + A @ B`` with the accumulator as an aliased kernel operand.

    Pallas/interpret route through ``bsr_spmm_acc_pallas`` (the running
    accumulator's buffer is donated and input/output-aliased — no fresh C
    allocation per consumed round); the ref path replays the same
    ascending-slot addition chain ``((acc + d_0) + d_1) + ...`` one slot
    at a time, so all three backends stay bit-compatible with the staged
    executors' accumulation order.
    """
    be = kernel_backend()
    if be in ("pallas", "interpret"):
        return bsr_spmm_acc_pallas(block_cols, blocks, b, acc,
                                   bn=min(bn, b.shape[1]),
                                   interpret=(be == "interpret"))
    for t in range(block_cols.shape[1]):
        acc = acc + _ref.bsr_spmm_ref(block_cols[:, t:t + 1],
                                      blocks[:, t:t + 1], b)
    return acc


@functools.partial(jax.custom_jvp, nondiff_argnums=(2,))
def _gather_rows(b: jax.Array, idx: jax.Array, bn: int) -> jax.Array:
    be = kernel_backend()
    if be == "pallas":
        return gather_rows_pallas(b, idx, bn=bn)
    if be == "interpret":
        return gather_rows_pallas(b, idx, bn=bn, interpret=True)
    return _ref.gather_rows_ref(b, idx)


@_gather_rows.defjvp
def _gather_rows_jvp(bn, primals, tangents):
    b, idx = primals
    b_dot, _ = tangents
    # linear in b: the tangent is the same gather, via the transposable
    # jnp oracle (reverse mode transposes it to a scatter-add)
    return _gather_rows(b, idx, bn), _ref.gather_rows_ref(b_dot, idx)


def gather_rows_op(b: jax.Array, idx: jax.Array, *, bn: int = 512) -> jax.Array:
    return _gather_rows(b, idx, bn)


def scatter_add_rows_op(c: jax.Array, partials: jax.Array, tgt: np.ndarray) -> jax.Array:
    """tgt is a STATIC (host-side) target map — plans are offline in SHIRO."""
    be = kernel_backend()
    if be == "ref":
        return _ref.scatter_add_rows_ref(c, partials, jnp.asarray(tgt))
    perm, meta = prepare_sorted_scatter(np.asarray(tgt))
    return scatter_add_rows_sorted_pallas(
        c, partials[jnp.asarray(perm)], jnp.asarray(meta),
        interpret=(be == "interpret"),
    )


def pack_rows_op(b: jax.Array, idx: jax.Array) -> jax.Array:
    """Executor-side comm-buffer pack: ``out[..., s, :] = b[idx[..., s]]``.

    ``idx`` may carry leading layout axes (e.g. [P, max_b] in the
    single-round schedule); the Pallas gather kernel runs on the
    flattened slot axis and the result is reshaped back. Slots with
    ``idx < 0`` (plan padding) come back zeroed.
    """
    flat = idx.reshape(-1)
    out = gather_rows_op(b, flat)
    return out.reshape(idx.shape + (b.shape[1],))


@jax.custom_jvp
def scatter_add_rows_exec_op(c: jax.Array, partials: jax.Array,
                             tgt: jax.Array, perm: jax.Array,
                             meta: jax.Array) -> jax.Array:
    """Executor-side result aggregation: ``c[tgt[s]] += partials[s]``.

    Unlike ``scatter_add_rows_op`` the sorted-scatter preparation has
    already happened host-side (once per plan, see
    ``prepare_sorted_scatter``) and ``perm`` / ``meta`` arrive as device
    arrays — required inside shard_map bodies where every process owns a
    different target map. ``tgt`` is only consulted by the jnp oracle
    path; the Pallas path consumes the pre-sorted ``perm`` / ``meta``.
    """
    be = kernel_backend()
    if be == "ref":
        return _ref.scatter_add_rows_ref(c, partials, tgt)
    return scatter_add_rows_sorted_pallas(
        c, partials[perm], meta, interpret=(be == "interpret"))


# on the TPU path, buckets up to this width fold in one unrolled chain of
# adds; wider ones loop over unrolled chunks of slots (``fold_chunk``)
FOLD_UNROLL = 128


def fold_chunk(w: int) -> int:
    """Slots one loop iteration of a ``w``-wide bucket folds, unrolled."""
    if w <= FOLD_UNROLL:
        return w
    u = min(max(w // 8, 64), 256)
    while w % u:
        u //= 2
    return u


def _fold_bucket(a: jax.Array, cols: jax.Array, vals: jax.Array,
                 b: jax.Array, w: int, fused: bool) -> jax.Array:
    """``a[r] + vals[0, r]·b[cols[0, r]] + vals[1, r]·b[cols[1, r]] + ...``,
    added one slot after another in ascending slot order.

    ``cols`` and ``vals`` are the bucket's ``[w, R]`` slots, slot-major,
    flattened. The terms are made first, ``[w, R, N]`` (a view of the
    gather's ``[w·R, N]`` when R is a multiple of 8), and then added.
    ``fused`` (the TPU) adds a bucket up to ``FOLD_UNROLL`` wide in one
    unrolled chain, which the TPU compiler runs as a few fusions after
    one op of products, and a wider one in a loop over unrolled chunks
    of slots.
    Otherwise a loop adds one slot a trip: the CPU compiler contracts a
    product and the add that consumes it into one fused multiply-add,
    which rounds once where the scatter-add rounds twice, and a loop
    body sees only loaded terms. A loop of one trip is inlined, so there
    a one-slot bucket adds an exact zero as a second. An optimization
    barrier before the adds is no way to one form for both: the CPU
    compiler drops it, and on the TPU it makes the program six times
    larger.
    """
    terms = (_rows(b, cols) * vals[:, None]).reshape(w, -1, b.shape[1])
    if fused and w <= FOLD_UNROLL:
        return _add_chain(a, terms)
    if not fused:
        if w == 1:
            terms = jnp.pad(terms, ((0, 1), (0, 0), (0, 0)))
        a, _ = jax.lax.scan(_add_term, a, terms)
        return a
    u = fold_chunk(w)
    a, _ = jax.lax.scan(_add_chunk, a,
                        terms.reshape((w // u, u) + terms.shape[1:]))
    return a


def _rows(b: jax.Array, idx: jax.Array) -> jax.Array:
    """``b[idx]`` for row ids known to be in range."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(idx.ndim,), collapsed_slice_dims=(0,),
        start_index_map=(0,))
    return jax.lax.gather(b, idx[..., None], dnums, (1, b.shape[1]),
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _add_chain(a, terms):
    """``a + terms[0] + terms[1] + ...``, unrolled."""
    a = a[None]
    for t in jax.lax.split(terms, (1,) * terms.shape[0]):
        a = a + t
    return a[0]


def _add_term(a, t):
    return a + t, None


def _add_chunk(a, terms):
    return _add_chain(a, terms), None


@functools.partial(jax.custom_jvp, nondiff_argnums=(6,))
def coo_accumulate_rows_op(acc: jax.Array, fold, row: jax.Array,
                           col: jax.Array, val: jax.Array, b: jax.Array,
                           zero_acc: bool = False) -> jax.Array:
    """``acc[row[e]] += val[e]·b[col[e]]``, folded row by row, no scatter.

    ``fold`` is the piece's degree-bucketed row layout, built once on the
    host (``core.local_backend.RowFold``): per bucket of width ``w`` the
    ids of its rows, and slot-major, per slot of each row, the column
    and value of the nonzero it holds, in the row's own COO order
    (padding slots hold 0); ``perm`` maps each row of C to its bucket
    output row, or to -1 for a row with no nonzeros, which keeps its
    accumulator row.

    Each bucket starts from its rows of ``acc`` and adds the slots in
    ascending order, one after another: per row, the same addition chain
    the COO scatter-add ``acc.at[row].add(val·b[col])`` runs (padding
    adds an exact zero). So ``compute`` (the chain started from zeros)
    and the overlapped executors' per-round ``compute_segment`` (the
    chain resumed) stay bit-identical, and the result gathers back into
    row order instead of scattering.

    The JVP is the COO expression in ``row``, ``col`` and ``val``, linear
    in ``b`` and ``val``: reverse mode transposes it into the
    gather/scatter-add backward. ``fold``'s values must be ``val`` read
    through ``src`` (``CooBackend.with_values`` keeps them so).
    ``zero_acc`` says that ``acc`` is all zeros: then the fold reads none
    of it.
    """
    return fold_rows(acc, fold, b, fused=jax.default_backend() == "tpu",
                     zero_acc=zero_acc)


@functools.partial(jax.jit, static_argnames=("fused", "zero_acc"))
def fold_rows(acc: jax.Array, fold, b: jax.Array, *,
              fused: bool, zero_acc: bool = False) -> jax.Array:
    """The primal of ``coo_accumulate_rows_op``; ``fused`` as in
    ``_fold_bucket``. Jitted, so that outside an enclosing jit (an eager
    ``shard_map`` body) the fold compiles once and not op by op."""
    perm = fold.perm
    if perm.shape[0] != acc.shape[0]:
        raise ValueError(f"the piece has {perm.shape[0]} rows, the "
                         f"accumulator {acc.shape[0]}")
    if not fold.buckets:
        return acc
    # the scatter-add's chain: terms in the promoted dtype, rounded to
    # the accumulator's once, at the end
    dt = jnp.result_type(b.dtype, fold.val.dtype)
    outs, r0, s0 = [], 0, 0
    for w, n in fold.buckets:
        a = (jnp.zeros((n, b.shape[1]), dt) if zero_acc
             else _rows(acc, fold.rows[r0:r0 + n]).astype(dt))
        outs.append(_fold_bucket(a, fold.col[s0:s0 + w * n],
                                 fold.val[s0:s0 + w * n], b, w, fused))
        r0, s0 = r0 + n, s0 + w * n
    if zero_acc:
        # rows with no nonzeros read an appended zero row
        outs.append(jnp.zeros((1, b.shape[1]), dt))
        at = jnp.where(perm < 0, r0, perm)
        return _rows(jnp.concatenate(outs, axis=0), at).astype(acc.dtype)
    rows = _rows(jnp.concatenate(outs, axis=0), jnp.maximum(perm, 0))
    return jnp.where((perm >= 0)[:, None], rows.astype(acc.dtype), acc)


def _coo_accumulate_rows_jvp(zero_acc, primals, tangents):
    acc, fold, row, col, val, b = primals
    acc_dot, _, _, _, val_dot, b_dot = tangents
    out = coo_accumulate_rows_op(acc, fold, row, col, val, b, zero_acc)
    terms = []
    if not isinstance(b_dot, SymbolicZero):
        terms.append(b_dot[col] * val[:, None])
    if not isinstance(val_dot, SymbolicZero):
        terms.append(b[col] * val_dot[:, None])
    base = (jnp.zeros_like(out) if isinstance(acc_dot, SymbolicZero)
            else acc_dot)
    if not terms:
        return out, base
    return out, base.at[row].add(sum(terms[1:], terms[0]))


coo_accumulate_rows_op.defjvp(_coo_accumulate_rows_jvp, symbolic_zeros=True)


@scatter_add_rows_exec_op.defjvp
def _scatter_add_rows_exec_jvp(primals, tangents):
    c, partials, tgt, perm, meta = primals
    c_dot, p_dot = tangents[0], tangents[1]
    # linear in (c, partials); integer plan maps carry no tangent
    out = scatter_add_rows_exec_op(c, partials, tgt, perm, meta)
    return out, _ref.scatter_add_rows_ref(c_dot, p_dot, tgt)
