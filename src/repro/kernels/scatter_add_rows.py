"""Pallas TPU kernel: result aggregation — ``C[tgt[s], :] += partials[s, :]``.

SHIRO's stage-⑤ hot spot (paper §5.1): received partial C rows are
scatter-added into the local output block. Random scatter is hostile to
TPU; the offline planner instead SORTS the receive slots by target row
(a static permutation — free at plan time), which turns the scatter into a
segmented reduction with *consecutive* revisits of each output tile:

  grid step s touches output block row tgt_sorted[s];
  first visit of a segment initializes from the aliased C input,
  later visits accumulate in VMEM (no HBM round-trip within a segment).

The C argument is donated and aliased to the output, so untouched rows
keep their values without any copy. ``tgt`` must be sorted ascending with
-1 (dropped pads) sorted to the END and clamped to row 0 contributing
zeros — ``prepare_sorted_scatter`` below does this host-side.

Both row operands are viewed as ``[rows, 1, n]`` so that each one-row
block spans the array's full second-minor dim, which the TPU compiler
requires of blocks that are not (8, 128)-aligned (see ``gather_rows``).
``meta`` is prefetched into SMEM in chunks of slots (``kernels.prefetch``);
each chunk's call takes the previous one's output as its aliased C, so a
segment cut by a chunk boundary resumes from its partial sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .prefetch import chunks

__all__ = ["scatter_add_rows_sorted_pallas", "prepare_sorted_scatter"]


def prepare_sorted_scatter(tgt: np.ndarray):
    """Host-side slot preparation. Returns (perm, meta).

    Slots are sorted by target row with pads (-1) last; pads are then
    re-pointed at the LAST real target so at kernel time they join its
    segment as zero contributions instead of opening a fresh segment (a
    fresh segment would re-initialize that row from the pre-kernel C and
    lose earlier accumulation). ``meta`` = [tgt_sorted..., n_valid].
    """
    tgt = np.asarray(tgt)
    key = np.where(tgt < 0, np.iinfo(np.int32).max, tgt)
    perm = np.argsort(key, kind="stable").astype(np.int32)
    tgt_sorted = tgt[perm].astype(np.int32)
    n_valid = int((tgt_sorted >= 0).sum())
    fill = tgt_sorted[n_valid - 1] if n_valid > 0 else 0
    tgt_sorted[n_valid:] = fill
    meta = np.concatenate([tgt_sorted, np.asarray([n_valid], np.int32)])
    return perm, meta


def _kernel(meta_ref, part_ref, c_ref, out_ref, *, s_total: int):
    s = pl.program_id(0)
    n_valid = meta_ref[s_total]
    t = meta_ref[s]
    prev = meta_ref[jnp.maximum(s - 1, 0)]
    new_segment = jnp.logical_or(s == 0, t != prev)
    part = part_ref[...]
    contrib = jnp.where(s < n_valid, part, jnp.zeros_like(part))

    @pl.when(new_segment)
    def _init():
        out_ref[...] = c_ref[...] + contrib

    @pl.when(jnp.logical_not(new_segment))
    def _acc():
        out_ref[...] += contrib


def _scatter(c3: jax.Array, parts3: jax.Array, meta: jax.Array, lo: int,
             interpret: bool) -> jax.Array:
    s_total = meta.shape[0] - 1
    m, _, n = c3.shape
    row = (pl.Squeezed(), 1, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_total,),
        in_specs=[
            pl.BlockSpec(row, lambda s, meta: (s + lo, 0, 0)),
            pl.BlockSpec(row, lambda s, meta: (meta[s], 0, 0)),
        ],
        out_specs=pl.BlockSpec(row, lambda s, meta: (meta[s], 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, s_total=s_total),
        name="scatter_add_rows",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, n), c3.dtype),
        interpret=interpret,
        input_output_aliases={2: 0},  # alias C (arg index counts scalar first)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
    )(meta, parts3, c3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_add_rows_sorted_pallas(
    c: jax.Array,  # [M, n] — donated/aliased to the output
    partials_sorted: jax.Array,  # [S, n], already permuted by prepare_sorted_scatter
    meta: jax.Array,  # [S+1] int32: sorted targets (pads re-pointed) + n_valid
    *,
    interpret: bool = False,
) -> jax.Array:
    s_total = partials_sorted.shape[0]
    m, n = c.shape
    parts3 = partials_sorted.reshape(s_total, 1, n)
    out = c.reshape(m, 1, n)
    n_valid = meta[s_total]
    for lo, hi in chunks(s_total):
        valid = jnp.clip(n_valid - lo, 0, hi - lo).astype(meta.dtype)
        out = _scatter(out, parts3, jnp.concatenate([meta[lo:hi], valid[None]]),
                       lo, interpret)
    return out.reshape(m, n)
