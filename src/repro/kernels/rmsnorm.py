"""Pallas TPU kernel: fused RMSNorm — ``y = x * rsqrt(mean(x²)+eps) * g``.

Unfused, RMSNorm costs 4+ HBM round-trips of the activation (square, mean,
rsqrt-mul, scale-mul); the §Roofline memory terms showed elementwise
chains like this are a real share of the per-layer bytes. The fused kernel
reads each activation row tile once and writes once, with the reduction in
fp32 VMEM scratch.

Grid: (rows // br,). Block: (br, D) — the full feature dim stays in VMEM
(all assigned archs have D ≤ 8192 → ≤ 4 MB fp32 per 128-row tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rmsnorm_pallas"]


def _kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "eps", "interpret"))
def rmsnorm_pallas(x: jax.Array, gain: jax.Array, *, eps: float = 1e-5,
                   br: int = 128, interpret: bool = False) -> jax.Array:
    """x: [..., D] (leading dims flattened to rows), gain: [D]."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    brr = min(br, rows)
    if rows % brr:
        brr = rows  # odd smoke shapes: single tile
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        name="rmsnorm",
        grid=(rows // brr,),
        in_specs=[
            pl.BlockSpec((brr, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((brr, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(x2, gain)
    return out.reshape(shape)
