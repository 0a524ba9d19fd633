"""Service loop: the share of coalesced windows dispatched through the
session's fused serve step, of all dispatches (MetricsRegistry counters
``dispatch.fused``, ``dispatch.batched``, ``dispatch.sequential``), in %."""


def read(record, trace):
    c = record.get("counters", {})
    fused = c.get("dispatch.fused", 0)
    total = fused + c.get("dispatch.batched", 0) + c.get(
        "dispatch.sequential", 0)
    return 100.0 * fused / total if total else None
