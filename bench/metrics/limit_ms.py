"""Service: host milliseconds per batch choosing the LIMIT cuts.

The program's ``prune.limit.select`` spans (``core/flow.py``), one per
LIMIT query inside ``prune.technique.limit``: picking the fully-matching
partitions whose rows reach k, and the cut to them (paper Sec. 4).  FULL
marks are what it works from, so it moves where they do.  Summed per
``prune.run_batch``; the median over the window's batches.  Nothing on a
program without the span.
"""

from bench import stages

SPAN = stages.PREFIX + "limit.select"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    if not any(ev[0] == SPAN for ev in run.trace.host):
        return None
    return stages.per_batch_ms(run.trace, ("limit.select",))
