"""Wrappers: host milliseconds per batch spent copying launch results to
the host.

The program's ``prune.d2h`` spans (``kernels/ops.py``): ``np.asarray`` of
a launch's result once ``jax.block_until_ready`` has returned, so the
transfer and any relayout, without the wait for the device's compute
(``prune.device_wait``).  Summed per ``prune.run_batch``; the median
over the window's batches.
"""

from bench import stages


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return stages.per_batch_ms(run.trace, ("d2h",))
