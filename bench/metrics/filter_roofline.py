"""Kernel ``minmax_prune_batched`` (the flat filter): share of its roofline.

The least time is the bytes any implementation must move, over the chip's
HBM bandwidth: per flat filter launch, each distinct predicate column's
three f32 stat planes (min, max, demote) over the live partitions, plus
each query's three-valued verdict per live partition at 2 bits.  The
kernel's time is the summed device time of its operations in the trace.
Only batches that ran the flat kernel count, so a later kernel that
moves less can approach but not pass 100%.
"""

from bench import tracing

KERNEL_NAMES = ("minmax_prune_batched",)


def least_bytes(batch) -> float:
    return sum(t["cols"] * 3 * 4 * t["P"] + t["q"] * t["P"] / 4
               for t in batch["tables"].values())


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    counted = [b for b in run.batches if b.get("flat_filter_launches", 0) > 0]
    nbytes = sum(least_bytes(b) for b in counted)
    secs = tracing.kernel_s(run.trace, KERNEL_NAMES)
    if nbytes <= 0 or secs <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / secs
