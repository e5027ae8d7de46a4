"""Device ms per call of the ops under the executor's ``pack`` step
(``shiro.spmm`` / ``pack``: the gather of B rows into the send buffer),
mean over the chips; none on one chip."""
from chipbench import steps


def read(rec):
    return steps.step_ms(rec, "spmm", "pack", "call")
