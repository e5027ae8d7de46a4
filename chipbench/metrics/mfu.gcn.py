"""A training step's operations times steps per second, over the chips' peak, in %."""
from chipbench import readers


def read(rec):
    if rec["unit"] != "step" or not readers.completed(rec):
        return None
    rate = readers.completed(rec) / rec["window_s"]
    return 100.0 * rec["work"]["flops"] * rate / (rec["chips"] * rec["peaks"]["flops_per_s"])
