"""Sparse operands of the benchmark's configurations, made from a seed.

Each generator takes a configuration (the parsed ``configs/<name>.json``)
and a seed and returns a ``Graph``: the canonical COO arrays of the square
sparse operand, sorted by (row, col) with no duplicates. A configuration
names its generator under ``"generator"``; ``GENERATORS`` maps the name to
the function, so a configuration with a generator already here is added
as a data file alone.

These are the benchmark's own copies, kept apart from the program so that
no change to the program changes the yardstick:

* ``power_law_edges`` is ``repro.core.sparse.power_law_graph`` (seeded
  Zipf-weighted endpoints over shuffled ids, drawn until exactly ``nnz``
  distinct edges exist) and ``gcn_normalize`` is
  ``repro.models.gnn.normalize_adjacency`` (self loops, then
  D^-1/2 (A + I) D^-1/2); the two give the same arrays as the program's.
* ``delaunay_share`` triangulates seeded uniform points of the unit square
  and numbers them along a Morton curve.
"""
from __future__ import annotations

import dataclasses

import numpy as np

GENERATOR_VERSION = 1


@dataclasses.dataclass(frozen=True)
class Graph:
    """A square sparse operand as canonical COO arrays."""

    n: int
    row: np.ndarray  # int32, sorted by (row, col)
    col: np.ndarray  # int32
    val: np.ndarray  # float32

    @property
    def nnz(self) -> int:
        return int(self.row.size)


def _canonical(n: int, row, col, val) -> Graph:
    key = np.asarray(row, np.int64) * n + np.asarray(col, np.int64)
    order = np.argsort(key, kind="stable")
    key = key[order]
    if key.size and np.any(key[1:] == key[:-1]):
        raise ValueError("operand has duplicate (row, col) entries")
    return Graph(n, (key // n).astype(np.int32), (key % n).astype(np.int32),
                 np.asarray(val, np.float32)[order])


def power_law_edges(n: int, nnz: int, alpha: float, seed: int,
                    max_rounds: int = 64) -> tuple:
    """``nnz`` distinct directed edges, no self loops, with Zipf endpoint
    weights ``rank ** -alpha`` over node ids shuffled from ``seed``."""
    if not 0 < nnz <= n * (n - 1):
        raise ValueError(f"nnz={nnz} must be in (0, n*(n-1)] for n={n}")
    rng = np.random.default_rng(seed)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    w /= w.sum()
    row_ids, col_ids = rng.permutation(n), rng.permutation(n)
    keys = np.empty(0, np.int64)
    for _ in range(max_rounds):
        short = nnz - keys.size
        if short <= 0:
            break
        draw = short + short // 4 + 1024
        r = row_ids[rng.choice(n, size=draw, p=w)].astype(np.int64)
        c = col_ids[rng.choice(n, size=draw, p=w)].astype(np.int64)
        keys = np.union1d(keys, (r * n + c)[r != c])
    else:
        raise ValueError(f"{keys.size} distinct edges after {max_rounds} rounds, "
                         f"short of nnz={nnz}")
    keys = np.sort(rng.choice(keys, size=nnz, replace=False))
    return keys // n, keys % n


def gcn_normalize(n: int, row, col) -> Graph:
    """D^-1/2 (A + I) D^-1/2 of a 0/1 adjacency (Kipf and Welling)."""
    loops = np.arange(n, dtype=np.int64)
    row = np.concatenate([np.asarray(row, np.int64), loops])
    col = np.concatenate([np.asarray(col, np.int64), loops])
    deg = np.bincount(row, minlength=n).astype(np.float64)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1e-12))
    return _canonical(n, row, col, dinv[row] * dinv[col])


def power_law_gcn(cfg: dict, seed: int) -> Graph:
    """The normalized power-law graph; with ``num_rows`` above ``num_nodes``,
    empty rows and columns follow the nodes (rows for chips to divide)."""
    n = int(cfg["num_nodes"])
    row, col = power_law_edges(n, int(cfg["num_edges"]), float(cfg["zipf_alpha"]), seed)
    g = gcn_normalize(n, row, col)
    return dataclasses.replace(g, n=int(cfg.get("num_rows", n)))


def morton_order(points: np.ndarray, bits: int = 16) -> np.ndarray:
    """Indices that sort points of the unit square along a Z-order curve."""
    q = np.clip((points * (1 << bits)).astype(np.uint64), 0, (1 << bits) - 1)

    def spread(x):
        for shift, mask in ((8, 0x00FF00FF), (4, 0x0F0F0F0F), (2, 0x33333333),
                            (1, 0x55555555)):
            x = (x | (x << np.uint64(shift))) & np.uint64(mask)
        return x

    return np.argsort(spread(q[:, 0]) | (spread(q[:, 1]) << np.uint64(1)), kind="stable")


def delaunay_share(cfg: dict, seed: int) -> Graph:
    """Both directions of every Delaunay edge of ``num_rows`` seeded uniform
    points, numbered along a Morton curve; unit values, no self loops."""
    from scipy.spatial import Delaunay

    n = int(cfg["num_rows"])
    pts = np.random.default_rng(seed).random((n, 2))
    pts = pts[morton_order(pts)]
    tri = Delaunay(pts).simplices.astype(np.int64)
    a = np.concatenate([tri[:, 0], tri[:, 1], tri[:, 2]])
    b = np.concatenate([tri[:, 1], tri[:, 2], tri[:, 0]])
    und = np.unique(np.minimum(a, b) * n + np.maximum(a, b))
    lo, hi = und // n, und % n
    row, col = np.concatenate([lo, hi]), np.concatenate([hi, lo])
    return _canonical(n, row, col, np.ones(row.size, np.float32))


GENERATORS = {"power_law_gcn": power_law_gcn, "delaunay_share": delaunay_share}


def make(cfg: dict, seed: int) -> Graph:
    """The operand ``cfg`` describes, from ``seed``."""
    return GENERATORS[cfg["generator"]](cfg, seed)
