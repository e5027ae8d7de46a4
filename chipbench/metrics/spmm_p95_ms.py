"""95th percentile of the latency of every h(b) call in the window (host clock)."""
import numpy as np


def read(rec):
    lat = rec["latencies_s"]
    return float(np.percentile(lat, 95)) * 1e3 if rec["unit"] == "call" and lat else None
