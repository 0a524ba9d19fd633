"""Device serve step: the chip's busy time per serve step, from the
profiler trace of the window (ms per step, averaged over the chips)."""


def read(record, trace):
    if not trace or not record.get("steps") or not trace.get("busy_s"):
        return None
    return 1e3 * trace["busy_s"] / record["steps"]
