"""Transport: the share of offered frames the sender popped and dropped
because they could no longer meet the latency bound (counter
``sender.expired`` over frames offered), in %."""


def read(record, trace):
    offered = record.get("offered", 0)
    if not offered:
        return None
    return 100.0 * record.get("counters", {}).get("sender.expired", 0) / offered
