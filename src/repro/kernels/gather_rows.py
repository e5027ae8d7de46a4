"""Pallas TPU kernel: comm-buffer pack — ``out[s, :] = B[idx[s], :]``.

This is SHIRO's communication stage-① hot spot: before any B-row transfer
(flat column-based or hierarchical inter-group fetch) the selected rows are
packed into a contiguous send buffer. On GPU this is a gather kernel; on
TPU a scalar-prefetched index map fetches one source row per grid step,
so the gather overlaps the pipeline's tile copies (HBM→VMEM) instead of
issuing random accesses from compute.

Rows are viewed as ``[rows, 1, n]``: the TPU compiler accepts a block
only when its last two dims are (8k, 128k) multiples or equal the
array's own, and a one-row block of a 2-D ``[rows, n]`` array is
neither. In the 3-D view the block ``(1, bn)`` spans the full second-
minor dim, and XLA lays such arrays out in (1, 128) tiles, so the view
costs a relayout copy, not padding to 8 sublanes. ``idx`` is prefetched
into SMEM in chunks of slots (``kernels.prefetch``).

Padding: idx < 0 → output row zeroed (the send slot is a plan pad).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .prefetch import chunks

__all__ = ["gather_rows_pallas"]


def _kernel(idx_ref, b_ref, out_ref):
    s = pl.program_id(0)
    row = b_ref[...]  # [1, bn] tile of the prefetched source row
    out_ref[...] = jnp.where(idx_ref[s] >= 0, row, jnp.zeros_like(row))


def _gather(b3: jax.Array, idx: jax.Array, bn: int, interpret: bool) -> jax.Array:
    s_total = idx.shape[0]
    n = b3.shape[2]
    row = (pl.Squeezed(), 1, bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_total, n // bn),
        in_specs=[
            pl.BlockSpec(row, lambda s, j, idx: (jnp.maximum(idx[s], 0), 0, j)),
        ],
        out_specs=pl.BlockSpec(row, lambda s, j, idx: (s, 0, j)),
    )
    return pl.pallas_call(
        _kernel,
        name="gather_rows",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_total, 1, n), b3.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel"),
        ),
    )(idx, b3)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def gather_rows_pallas(
    b: jax.Array,  # [K, n]
    idx: jax.Array,  # [S] int32, -1 padded
    *,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns out [S, n] with out[s] = b[idx[s]] (zeros where idx < 0)."""
    s_total = idx.shape[0]
    k, n = b.shape
    if n % bn:
        bn = n  # fall back to full-row tiles for narrow matrices
    b3 = b.reshape(k, 1, n)
    outs = [_gather(b3, idx[lo:hi], bn, interpret) for lo, hi in chunks(s_total)]
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out.reshape(s_total, n)
