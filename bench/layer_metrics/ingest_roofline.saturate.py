"""Ingest kernel: the least time its calls need (the bytes of
``bench/work.py`` at the chip's HBM peak; the kernel is bytes-bound)
over the kernel's device time in the trace, in %. Per chip: a camera
mesh gives each chip its share of the cameras."""
from bench import work


def read(record, trace):
    if not trace or not trace.get("kernel_s") or not trace.get(
            "kernel_calls"):
        return None
    cfg, shape = record["config"], record["shape"]
    per_chip = shape["cameras"] // trace["chips"]
    bs, bv = cfg["bins"]
    nbytes = work.ingest_bytes(per_chip, shape["frames"],
                               shape["height"] * shape["width"],
                               len(cfg["query"]["colors"]), bs * bv)
    least = work.least_seconds(nbytes, record["peaks"]) * trace["kernel_calls"]
    return 100.0 * least / trace["kernel_s"]
