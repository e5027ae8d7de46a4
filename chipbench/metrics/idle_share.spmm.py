"""Share of the traced window in which no op ran on the device, mean over chips, in %."""
from chipbench import readers


def read(rec):
    return readers.idle_pct(rec, "call")
