"""The least work of a unit of a cell, and the chip's peaks to set it against.

The counts are algorithmic: they are the same whatever backend, schedule or
padding the program uses, and they are lower bounds, so that a roofline
share computed from them cannot pass 100%.

* One float32 SpMM C[M, N] = A[M, K] @ B[K, N] with ``nnz`` nonzeros does
  2 nnz N operations and must at least read A once as COO (row, col, val:
  12 bytes a nonzero), read B once and write C once: 12 nnz + 4 N (K + M)
  bytes.
* One full-batch GCN training step (``h <- Â (h W + b)`` per layer, plain
  SGD) does, per layer of widths f_in -> f_out, one SpMM at f_out forward
  and its transpose at f_out backward, the product h W forward, dW = hᵀ dz
  backward and, for every layer but the first, dh = dz Wᵀ. Each product
  counts 2 M f_in f_out operations and the bytes of its two large operands
  and its result, read or written once.

``peaks(device_kind)`` reads ``peaks.json``; a kind that is not in the table
is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def spmm_work(nnz: int, m: int, k: int, n: int) -> dict:
    return {"flops": 2.0 * nnz * n, "bytes": 12.0 * nnz + 4.0 * n * (k + m)}


def gcn_step_work(nnz: int, nodes: int, widths: list) -> dict:
    """``widths`` = [f_0, f_1, ..., f_L]: the features and each layer's width."""
    flops = bytes_ = 0.0
    for layer, (f_in, f_out) in enumerate(zip(widths[:-1], widths[1:])):
        sp = spmm_work(nnz, nodes, nodes, f_out)
        flops += 2 * sp["flops"]
        bytes_ += 2 * sp["bytes"]
        products = [(f_in, f_out), (f_in, f_out)]  # h W, then dW = hᵀ dz
        if layer:
            products.append((f_out, f_in))  # dh = dz Wᵀ
        for a, b in products:
            flops += 2.0 * nodes * a * b
            bytes_ += 4.0 * nodes * (a + b)
    return {"flops": flops, "bytes": bytes_}


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"({PEAKS_FILE.name}) has {sorted(table)}")
    return table[device_kind]


def least_time_s(work: dict, peak: dict, chips: int) -> tuple:
    """(seconds, bound): the least time ``chips`` chips need for ``work``
    split evenly over them, and which peak sets it."""
    t_flops = work["flops"] / (chips * peak["flops_per_s"])
    t_bytes = work["bytes"] / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "hbm_bytes") if t_bytes >= t_flops else (t_flops, "flops")
