"""Device: the share of the traced window in which no operation ran on
the chip (1 - busy / window, averaged over the chips used), in %."""


def read(record, trace):
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
