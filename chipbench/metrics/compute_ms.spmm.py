"""Device ms per call of every op that is not a collective, mean over chips."""
from chipbench import readers


def read(rec):
    return readers.device_ms_per_unit(rec, "other_s", "call")
