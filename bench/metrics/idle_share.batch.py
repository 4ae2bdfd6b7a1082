"""Device: share of the traced window with no operation on the chip
(closed-loop cells).  1 - union of device-operation intervals / window."""

from bench import tracing


def read(run):
    return tracing.idle_percent(run.trace)
