"""Device ms per call of the ops under the executor's ``aggregate`` step
(``shiro.spmm`` / ``aggregate``: the scatter-add of received partial C
rows into C), mean over the chips; none on one chip."""
from chipbench import steps


def read(rec):
    return steps.step_ms(rec, "spmm", "aggregate", "call")
