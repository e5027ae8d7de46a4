"""Pallas TPU kernel: block-sparse-row (BSR/ELL) SpMM — ``C = A @ B``.

TPU adaptation of the paper's cuSPARSE CSR SpMM (DESIGN.md §2): instead of
per-row gathers (GPU idiom, hostile to the MXU), A is stored as dense
(bm × bk) blocks in an ELL layout — ``block_cols[mb, t]`` names the block
column of the t-th stored block in block-row mb (−1 = padding, its block is
all-zero). Every stored block feeds the 128×128 MXU directly.

Grid: (mb, n_tiles, t). The B tile for step (i, j, t) is selected by a
*scalar-prefetched* index map reading ``block_cols[i, t]`` — the Pallas
equivalent of indirect addressing, resolved at tile-fetch time so the
pipeline can double-buffer the gather. The output tile (i, j) is revisited
across the innermost t axis and accumulated in VMEM (init at t == 0).

VMEM working set per step: bm·bk (A block) + bk·bn (B tile) + bm·bn (C
tile); with the default 128³ tiles that is 3·64 KiB of fp32 — comfortably
inside the ~16 MiB VMEM budget, leaving room for double buffering.

``block_cols`` is prefetched into SMEM flattened, in chunks of block rows
(``kernels.prefetch``): one ``pallas_call`` per chunk, each reading the
whole ``blocks`` / ``B`` operands through offset index maps and writing
its own rows of C.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .prefetch import chunks

__all__ = ["bsr_spmm_pallas", "bsr_spmm_acc_pallas", "block_dot"]


def block_dot(a: jax.Array, b: jax.Array, contract) -> jax.Array:
    """f32-accumulated dot of two blocks inside a kernel.

    Mosaic runs a dot of f32 operands as one bf16 pass unless asked for
    ``HIGHEST``; on a TPU v5e that left the bsr SpMM's C up to 1.5e-2 off
    an f32 reference. f32 operands therefore get ``HIGHEST``; bf16 ones
    keep the single pass.
    """
    f32 = jnp.float32 in (a.dtype, b.dtype)
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if f32 else None,
    )


def _kernel(cols_ref, blocks_ref, b_ref, out_ref, *, t_steps: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a_blk = blocks_ref[0, 0]  # [bm, bk]
    b_blk = b_ref[0]  # [bk, bn]
    # padded slots have all-zero A blocks, so no masking is needed; the
    # clamped index map only changes WHICH (ignored) B tile is prefetched.
    # The out tile is an f32 accumulator (MXU-native): bf16 inputs,
    # f32 partials — matches the ref.py oracle's accumulation order.
    out_ref[...] += block_dot(a_blk, b_blk, ((1,), (0,)))


def _bsr_call(kernel, block_cols, blocks, b3, acc, lo, hi, bn, interpret):
    """One chunk of block rows ``[lo, hi)``; ``acc`` (or None) is its C seed."""
    _, t_steps, bm, bk = blocks.shape
    n = b3.shape[2]
    cols = block_cols[lo:hi].reshape(-1)
    in_specs = [
        pl.BlockSpec((1, 1, bm, bk), lambda i, j, t, cols: (i + lo, t, 0, 0)),
        pl.BlockSpec(
            (1, bk, bn),
            lambda i, j, t, cols: (jnp.maximum(cols[i * t_steps + t], 0), 0, j),
        ),
    ]
    operands = [cols, blocks, b3]
    aliases = {}
    if acc is not None:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, t, cols: (i, j)))
        operands.append(acc)
        # operand index counts the scalar-prefetch arg: acc is input 3
        aliases = {3: 0}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hi - lo, n // bn, t_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t, cols: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        name="bsr_spmm" if acc is None else "bsr_spmm_acc",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(((hi - lo) * bm, n), jnp.float32),
        input_output_aliases=aliases,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(*operands)


def _concat(outs):
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def bsr_spmm_pallas(
    block_cols: jax.Array,  # [mb, t] int32, -1 padded
    blocks: jax.Array,  # [mb, t, bm, bk]
    b: jax.Array,  # [kb*bk, n]
    *,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns C = A @ B, shape [mb*bm, n]. ``n`` must divide by ``bn``."""
    mb, t_steps, bm, bk = blocks.shape
    n = b.shape[1]
    if n % bn:
        raise ValueError(f"n={n} must be a multiple of bn={bn}")
    b3 = b.reshape(-1, bk, n)  # block-row view [kb, bk, n]
    kernel = functools.partial(_kernel, t_steps=t_steps)
    out = _concat([_bsr_call(kernel, block_cols, blocks, b3, None, lo, hi, bn, interpret)
                   for lo, hi in chunks(mb, t_steps)])
    return out.astype(b.dtype)


def _acc_kernel(cols_ref, blocks_ref, b_ref, acc_ref, out_ref):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    a_blk = blocks_ref[0, 0]  # [bm, bk]
    b_blk = b_ref[0]  # [bk, bn]
    out_ref[...] += block_dot(a_blk, b_blk, ((1,), (0,)))


@functools.partial(jax.jit, static_argnames=("bn", "interpret"),
                   donate_argnames=("acc",))
def bsr_spmm_acc_pallas(
    block_cols: jax.Array,  # [mb, t] int32, -1 padded
    blocks: jax.Array,  # [mb, t, bm, bk]
    b: jax.Array,  # [kb*bk, n]
    acc: jax.Array,  # [mb*bm, n] f32 — consumed (donated + aliased)
    *,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns ``acc + A @ B`` with the accumulator as an aliased operand.

    The segment-accumulating form of ``bsr_spmm_pallas``: the running
    accumulator rides INTO the kernel as an input/output-aliased operand
    (its buffer is reused for the result — no fresh C allocation per
    round), and the per-slot accumulation chain is
    ``((acc + d_0) + d_1) + ...`` in ascending t order — bit-identical to
    looping ``acc = acc + bsr_spmm_pallas(slot_t)`` over the slots, which
    is what the overlapped executors' cumulative-prefix contract requires.
    ``acc`` is donated: callers must not reuse it after the call.
    """
    mb, t_steps, bm, bk = blocks.shape
    n = b.shape[1]
    if n % bn:
        raise ValueError(f"n={n} must be a multiple of bn={bn}")
    if acc.shape != (mb * bm, n):
        raise ValueError(f"acc shape {acc.shape} != {(mb * bm, n)}")
    b3 = b.reshape(-1, bk, n)  # block-row view [kb, bk, n]
    acc = acc.astype(jnp.float32)
    out = _concat([_bsr_call(_acc_kernel, block_cols, blocks, b3,
                             acc[lo * bm:hi * bm], lo, hi, bn, interpret)
                   for lo, hi in chunks(mb, t_steps)])
    return out.astype(b.dtype)
