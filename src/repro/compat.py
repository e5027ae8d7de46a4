"""The JAX API surface the distributed code imports (jax 0.9, Python 3.12).

One audited import surface for the mesh, ``shard_map`` and collective
calls, so the next JAX API migration lands in one file. Every function
is a thin wrapper over the installed spelling.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh

__all__ = [
    "shard_map",
    "make_mesh",
    "with_sharding_constraint",
    "all_to_all",
    "psum_scatter",
    "ppermute",
]


def shard_map(f: Callable, *, mesh: Mesh, in_specs, out_specs,
              check: bool = False) -> Callable:
    """``jax.shard_map`` with ``check`` as the ``check_vma`` flag.

    The repo always runs with it off: the SHIRO bodies use collectives
    whose varying-manual-axes rules the checker rejects spuriously.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """``jax.make_mesh`` with every axis explicitly ``AxisType.Auto``."""
    kwargs: dict = {}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), **kwargs)


def with_sharding_constraint(x, sharding):
    """Stable alias for ``jax.lax.with_sharding_constraint``."""
    return jax.lax.with_sharding_constraint(x, sharding)


# ---------------------------------------------------------------------------
# collectives — one audited import surface for the distributed code
# ---------------------------------------------------------------------------


def all_to_all(x: jax.Array, axis_name: str, split_axis: int = 0,
               concat_axis: int = 0, *, tiled: bool = False) -> jax.Array:
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis,
                              tiled=tiled)


def psum_scatter(x: jax.Array, axis_name: str, *, scatter_dimension: int = 0,
                 tiled: bool = True) -> jax.Array:
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=tiled)


def ppermute(x: jax.Array, axis_name: str,
             perm: Sequence[Tuple[int, int]]) -> jax.Array:
    """``jax.lax.ppermute`` — one (src, dst) matching = one collective.

    The bucketed communication schedules (core.comm_schedule) are built
    from shift permutations ``[(q, (q + d) % P) for q]``; receivers not
    named in ``perm`` get zeros, which is exactly the padding semantics
    the schedules rely on.
    """
    return jax.lax.ppermute(x, axis_name, perm)
