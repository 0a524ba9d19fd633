"""Load generator: the 95th percentile of how late the service took
each camera frame in against its scheduled capture instant (ms)."""
import numpy as np


def read(record, trace):
    late = record.get("gen_late_s")
    if not late:
        return None
    return float(np.percentile(np.asarray(late), 95) * 1e3)
