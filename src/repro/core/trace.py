"""Host spans of the served call, on the profiler's clock.

``span(name)`` records a host event named ``name`` on the same plane and
clock as the device trace when a ``jax.profiler`` trace runs, and costs
about a microsecond when none does. ``DistSpmm``'s spmm call opens three
sibling spans:

* ``shiro.dispatch``: operand validation, placement, the donation copy,
  the executable lookup and the launch;
* ``shiro.wait``: launching the guard's ``isfinite`` probe of C on the
  device and reading its result, which waits for C (only with ``check``
  on);
* ``shiro.guard``: the host sweep of the probe's scalars; the span
  carries ``host_bytes``, the bytes read back from devices.

The executors run under ``jax.named_scope("shiro.spmm")`` (see
``core.dist_spmm``); that name rides in the HLO's ``op_name`` metadata.
Inside their ``shard_map`` bodies each step runs under one more
``jax.named_scope``, named by ``STEPS`` (``core.dist_spmm`` says what each
holds). The names carry no ``shiro.`` prefix, so that a profile still
counts a step's ops as the executor's.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span", "PACK", "EXCHANGE", "COMPUTE", "AGGREGATE", "STEPS"]

PACK, EXCHANGE, COMPUTE, AGGREGATE = "pack", "exchange", "compute", "aggregate"
STEPS = (PACK, EXCHANGE, COMPUTE, AGGREGATE)


def span(name: str) -> TraceAnnotation:
    """A context manager that records a host span named ``name``; its
    ``set_metadata(**stats)`` attaches numbers to the span."""
    return TraceAnnotation(name)
