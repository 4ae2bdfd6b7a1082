"""Service: host milliseconds per batch turning the filter's ``[Q, P]``
verdicts into scan sets.

The host's two passes over the verdicts after the copy: ``prune.unpack``
(slice to ``[Q, P]``, narrow to int8, demote FULL where a bound was
inexact) and ``prune.filter.assemble`` (dead-partition mask, ``np.where``
and a ``ScanSet`` per distinct verdict row).  Summed per
``prune.run_batch``; the median over the window's batches.
"""

from bench import stages


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return stages.per_batch_ms(run.trace, ("unpack", "filter.assemble"))
