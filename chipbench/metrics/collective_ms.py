"""Device ms per call of collective ops (all-to-all, collective-permute,
all-reduce, reduce-scatter, all-gather), mean over the chips; none on one chip."""
from chipbench import readers


def read(rec):
    if rec["chips"] == 1:
        return None
    return readers.device_ms_per_unit(rec, "collective_s", "call")
