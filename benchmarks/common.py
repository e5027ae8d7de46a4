"""Shared benchmark utilities: dataset-family proxies + timing.

The paper's matrices (Tab. 2) are too large for this CPU container, so
each benchmark uses structure-matched synthetic proxies:
  social/web (com-YT, Orkut, uk-2002, ...) -> power-law on both sides;
  traffic (mawi)                           -> hub-structured;
  mesh/road (del24, EU)                    -> near-diagonal uniform.
Volume REDUCTIONS and scaling trends are structural properties of these
families, which is what the paper's figures measure.
"""
from __future__ import annotations

import time
from typing import Callable, Dict

import jax
import numpy as np

from repro.core.sparse import (
    CSRMatrix, coo_from_arrays, csr_from_coo, hub_sparse, power_law_sparse,
    random_sparse,
)

__all__ = ["DATASETS", "make_matrix", "time_call", "fmt_row"]


def _banded(m: int, k: int, band: int, density: float, seed: int) -> CSRMatrix:
    rng = np.random.default_rng(seed)
    nnz = int(m * band * density)
    row = rng.integers(0, m, nnz)
    off = rng.integers(-band, band + 1, nnz)
    col = np.clip(row + off, 0, k - 1)
    return csr_from_coo(coo_from_arrays((m, k), row, col))


DATASETS: Dict[str, Callable[[int], CSRMatrix]] = {
    # name -> builder(seed); shapes sized for CPU execution
    "social-pl": lambda s: power_law_sparse(1024, 1024, 16384, 1.35, s),
    "web-pl": lambda s: power_law_sparse(2048, 2048, 24576, 1.5, s),
    "mawi-hub": lambda s: hub_sparse(1024, 1024, 4, 4, 0.35, s),
    "mesh-band": lambda s: _banded(1024, 1024, 8, 0.8, s),
    "uniform": lambda s: random_sparse(1024, 1024, 0.01, s),
}


def make_matrix(name: str, seed: int = 0) -> CSRMatrix:
    return DATASETS[name](seed)


def time_call(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-time in microseconds."""
    for _ in range(warmup):
        r = fn(*args)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn(*args)
        jax.block_until_ready(r)
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def fmt_row(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.1f},{derived}"
