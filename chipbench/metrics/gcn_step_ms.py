"""Window seconds over completed training steps, loss read on the host (host clock)."""
from chipbench import readers


def read(rec):
    return readers.per_unit_ms(rec, "step")
