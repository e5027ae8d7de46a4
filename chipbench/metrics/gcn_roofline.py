"""Least time of one training step's work on the cell's chips over the device
busy time per step, in %."""
from chipbench import readers


def read(rec):
    return readers.roofline_pct(rec, "step")
