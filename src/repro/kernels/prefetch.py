"""SMEM budget of the kernels' scalar-prefetched index tables.

A scalar-prefetched operand is copied whole into SMEM, which holds 1 MiB
on a TPU v5e and also keeps the kernel's other scalars. A table with one
entry per row or per stored block of a real operand outgrows it: the
(8, 8) ELL table of a 169,343-row graph needs 10.8 MB. So every kernel
runs its grid in chunks whose slice of the table fits ``TABLE_ENTRIES``,
one ``pallas_call`` per chunk.
"""
from __future__ import annotations

from typing import List, Tuple

__all__ = ["TABLE_ENTRIES", "chunks"]

TABLE_ENTRIES = 64 * 1024  # int32 entries per pallas_call: 256 KiB of SMEM


def chunks(n_items: int, entries_per_item: int = 1) -> List[Tuple[int, int]]:
    """``[lo, hi)`` ranges over ``n_items`` grid items, each holding
    ``entries_per_item`` table entries; one empty range when there are none."""
    step = max(1, TABLE_ENTRIES // max(1, entries_per_item))
    return [(lo, min(lo + step, n_items)) for lo in range(0, max(n_items, 1), step)]
