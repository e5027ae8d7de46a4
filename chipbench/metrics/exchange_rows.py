"""Padded rows the chosen schedule moves between chips per call (planner counter)."""


def read(rec):
    rows = rec["counters"].get("volume_rows_padded")
    return float(rows) if rec["chips"] > 1 and rows is not None else None
