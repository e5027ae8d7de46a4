"""Window seconds over completed h(b) calls, each from h(b) to C ready (host clock)."""
from chipbench import readers


def read(rec):
    return readers.per_unit_ms(rec, "call")
