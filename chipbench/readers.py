"""Helpers of the metric readers in ``metrics/``.

A reader's record holds the run's host numbers (``setup_s``, ``spans``,
``window_s``, ``attempted``, ``failed``, and ``latencies_s`` of each
unit), the program's counters (``counters``), the least work of
one unit (``work``: ``flops``, ``bytes``), the chip's ``peaks``, the
cell's ``chips`` and ``unit`` (``call`` or ``step``), and in a traced run
the reduced trace (``trace``, see ``xplane.reduce``).
"""
from __future__ import annotations

from chipbench import work


def completed(rec: dict) -> int:
    return rec["attempted"] - rec["failed"]


def per_unit_ms(rec: dict, unit: str) -> float | None:
    """Window milliseconds over the completed units, in cells of ``unit``."""
    if rec["unit"] != unit or not completed(rec):
        return None
    return 1e3 * rec["window_s"] / completed(rec)


def device_mean_s(rec: dict, key: str) -> float | None:
    """A per-device trace number, summed over the cell's chips over their count."""
    if rec.get("trace") is None:
        return None
    return sum(d[key] for d in rec["trace"]["devices"].values()) / rec["chips"]


def device_ms_per_unit(rec: dict, key: str, unit: str) -> float | None:
    s = device_mean_s(rec, key)
    if s is None or rec["unit"] != unit or not completed(rec):
        return None
    return 1e3 * s / completed(rec)


def roofline_pct(rec: dict, unit: str) -> float | None:
    """The least time of one unit's work over the device's busy time per unit."""
    busy_ms = device_ms_per_unit(rec, "busy_s", unit)
    if not busy_ms:
        return None
    least, _bound = work.least_time_s(rec["work"], rec["peaks"], rec["chips"])
    return 100.0 * least / (busy_ms * 1e-3)


def idle_pct(rec: dict, unit: str) -> float | None:
    busy = device_mean_s(rec, "busy_s")
    if busy is None or rec["unit"] != unit:
        return None
    return 100.0 * (1.0 - busy / rec["trace"]["window_s"])
