"""Least time of one call's SpMM work on the cell's chips over the device busy
time per call, in %."""
from chipbench import readers


def read(rec):
    return readers.roofline_pct(rec, "call")
