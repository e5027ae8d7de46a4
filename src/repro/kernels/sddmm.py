"""Pallas TPU kernel: block-sparse SDDMM — ``vals = A ⊙ (X · Yᵀ)``.

The sampled dense-dense multiply is the dataflow REVERSE of
``kernels.bsr_spmm``: instead of folding stored blocks against gathered
dense tiles into C rows, each stored (bm × bk) block position samples the
dense outer product ``X_blk · Y_blkᵀ`` and scales it by the stored block
values (padding slots carry all-zero blocks, so they sample nothing and
need no masking). A is in the same ELL layout the SpMM kernel consumes
(``block_cols[mb, t]``, −1 = pad), which is what lets the fused
SDDMM→SpMM executor swap the sampled values straight back into the SpMM
kernel's operand without re-laying anything out.

Grid: (mb, t) — one program per stored block, no revisiting and no
accumulation. The Y tile for step (i, t) is selected by a scalar-
prefetched index map reading ``block_cols[i, t]`` (clamped; the clamp
only changes WHICH ignored tile is prefetched for padding slots). VMEM
working set per step: bm·f (X tile) + bk·f (Y tile) + 2·bm·bk (A block +
out block) — at 128-wide f that is well inside the VMEM budget.
``block_cols`` is prefetched into SMEM flattened, in chunks of block rows
(``kernels.prefetch``), as in ``kernels.bsr_spmm``.

``bsr_sddmm_ref`` is the pure-jnp oracle (single source of correctness
truth, as for every kernel in this package) and ``bsr_sddmm_op`` the
dispatching wrapper with a ``custom_jvp`` whose tangents run through the
oracle — ``pallas_call`` has no JVP, but SDDMM is bilinear in (X, Y) and
linear in the stored values, so training (the GAT layer differentiating
through a fused handle) works on every kernel backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bsr_spmm import block_dot
from .prefetch import chunks

__all__ = ["bsr_sddmm_ref", "bsr_sddmm_pallas", "bsr_sddmm_op"]


def bsr_sddmm_ref(block_cols: jnp.ndarray, blocks: jnp.ndarray,
                  x3: jnp.ndarray, y3: jnp.ndarray) -> jnp.ndarray:
    """Block-sparse SDDMM oracle.

    block_cols: [mb, t] int32, block-column id per stored block, -1 = pad
    blocks:     [mb, t, bm, bk] float, stored values (pads are zero)
    x3:         [mb, bm, f] dense rows, block-row view
    y3:         [kb, bk, f] dense rows, block-row view
    returns     [mb, t, bm, bk] = blocks ⊙ (x_blk · y_blkᵀ)
    """
    safe = jnp.maximum(block_cols, 0)
    y_g = y3[safe]  # [mb, t, bk, f]
    prod = jnp.einsum("mif,mtkf->mtik", x3.astype(jnp.float32),
                      y_g.astype(jnp.float32))
    return (blocks.astype(jnp.float32) * prod).astype(x3.dtype)


def _kernel(cols_ref, blocks_ref, x_ref, y_ref, out_ref):
    a_blk = blocks_ref[0, 0]  # [bm, bk]
    x_blk = x_ref[0]  # [bm, f]
    y_blk = y_ref[0]  # [bk, f]
    # sample the outer product at this block position; padding slots have
    # all-zero A blocks so the (arbitrary) prefetched Y tile is silenced
    # by the multiply — same no-masking property as the SpMM kernel
    out_ref[0, 0] = a_blk * block_dot(x_blk, y_blk, ((1,), (1,)))


def _sddmm_call(block_cols, blocks, x3, y3, lo, hi, interpret):
    """One chunk of block rows ``[lo, hi)`` of the sampled values."""
    _, t_steps, bm, bk = blocks.shape
    f = x3.shape[2]
    cols = block_cols[lo:hi].reshape(-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hi - lo, t_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bk), lambda i, t, cols: (i + lo, t, 0, 0)),
            pl.BlockSpec((1, bm, f), lambda i, t, cols: (i + lo, 0, 0)),
            pl.BlockSpec(
                (1, bk, f),
                lambda i, t, cols: (jnp.maximum(cols[i * t_steps + t], 0), 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bm, bk), lambda i, t, cols: (i, t, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        name="bsr_sddmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hi - lo, t_steps, bm, bk), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
    )(cols, blocks, x3, y3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_sddmm_pallas(
    block_cols: jax.Array,  # [mb, t] int32, -1 padded
    blocks: jax.Array,  # [mb, t, bm, bk]
    x3: jax.Array,  # [mb, bm, f]
    y3: jax.Array,  # [kb, bk, f]
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns ``blocks ⊙ (X · Yᵀ)`` sampled per stored block, f32.

    ``f`` (the contracted feature width) is unconstrained here; pad it to
    a lane multiple (128) for MXU efficiency on real hardware.
    """
    mb, t_steps, bm, bk = blocks.shape
    if t_steps == 0:  # empty piece: nothing stored, nothing sampled
        return jnp.zeros((mb, 0, bm, bk), jnp.float32)

    outs = [_sddmm_call(block_cols, blocks, x3, y3, lo, hi, interpret)
            for lo, hi in chunks(mb, t_steps)]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


@functools.partial(jax.custom_jvp, nondiff_argnums=(4, 5))
def _bsr_sddmm(block_cols, blocks, x3, y3, impl, interpret):
    if impl == "ref":
        return bsr_sddmm_ref(block_cols, blocks, x3, y3)
    out = bsr_sddmm_pallas(block_cols, blocks, x3, y3,
                           interpret=bool(interpret))
    return out.astype(x3.dtype)


@_bsr_sddmm.defjvp
def _bsr_sddmm_jvp(impl, interpret, primals, tangents):
    block_cols, blocks, x3, y3 = primals
    _, db, dx, dy = tangents
    out = _bsr_sddmm(block_cols, blocks, x3, y3, impl, interpret)
    # bilinear in (x, y), linear in the stored values; the integer plan
    # map carries no tangent. Tangents take the transposable jnp oracle
    # (reverse mode needs it — pallas_call has no transpose either).
    tan = (bsr_sddmm_ref(block_cols, db, x3, y3)
           + bsr_sddmm_ref(block_cols, blocks, dx, y3)
           + bsr_sddmm_ref(block_cols, blocks, x3, dy))
    return out, tan.astype(out.dtype)


def bsr_sddmm_op(block_cols: jax.Array, blocks: jax.Array, x3: jax.Array,
                 y3: jax.Array, *, impl: str = "pallas",
                 interpret: bool = False) -> jax.Array:
    """Dispatching SDDMM with oracle-backed derivatives.

    ``impl="ref"`` routes through the jnp oracle entirely; otherwise the
    Pallas kernel runs (interpret mode per ``interpret``) with tangents
    through the oracle, so the op differentiates on every platform.
    """
    return _bsr_sddmm(block_cols, blocks, x3, y3, impl, bool(interpret))
