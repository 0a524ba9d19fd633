"""Session step: the mean host time of ``ShedSession.step`` over the
window, from the call until its decisions are on the host (ms)."""
import numpy as np


def read(record, trace):
    steps = record.get("step_s")
    if not steps:
        return None
    return float(np.mean(steps) * 1e3)
