"""Production mesh construction (dry-run contract).

``make_production_mesh`` is a FUNCTION — importing this module never
touches jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE any jax
import (see launch/dryrun.py); everything else in the repo sees the real
single CPU device.

Axis semantics (DESIGN.md §5):
  pod   — slow tier (DCN between pods). SHIRO's inter-group axis.
  data  — fast tier (ICI inside a pod). Batch + FSDP + SHIRO intra-group.
  model — tensor/expert parallelism.
"""
from __future__ import annotations

from typing import Optional, Tuple

from jax.sharding import Mesh

from ..compat import make_mesh as _compat_make_mesh
from ..distributed.topology import Topology

__all__ = ["make_production_mesh", "make_mesh", "make_spmm_mesh"]


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with explicit Auto axis types (see repro.compat)."""
    return _compat_make_mesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_spmm_mesh(P: int, groups: Optional[int] = None) -> Mesh:
    """Mesh for the SHIRO SpMM executors: flat (x,) or two-tier (g, l).

    Thin wrapper over ``Topology.local(P)`` — the substrate naming moved
    to ``repro.distributed.topology``; this spelling remains for
    low-level code that wants a bare mesh.
    """
    topo = Topology.local(P)
    if groups is None:
        return topo.flat_mesh()[0]
    if P % groups:
        raise ValueError(f"P={P} not divisible by groups={groups}")
    return topo.hier_mesh(groups, P // groups)[0]
