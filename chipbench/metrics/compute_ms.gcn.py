"""Device busy ms per training step (union of op intervals), mean over chips."""
from chipbench import readers


def read(rec):
    return readers.device_ms_per_unit(rec, "busy_s", "step")
