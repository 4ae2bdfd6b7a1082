"""Service: host milliseconds per batch pruning on the host what the
device does not.

The program's spans of host-only pruning work: filter predicates that do
not lower to ranges (``prune.filter.host_eval``), join build sides
(``prune.join.build_keys``: the build scan's rows and key matches) and
their summaries (``prune.join.summarize``: distinct list or Bloom
filter), the probe side's pruning (``prune.join.apply``, the host matcher
included), and the ladder's host rungs (``prune.rung.host_kernel``,
``prune.rung.host_oracle``).  Summed per ``prune.run_batch``; the median
over the window's batches.
"""

from bench import stages

NAMES = ("filter.host_eval", "join.build_keys", "join.summarize",
         "join.apply", "rung.host_kernel", "rung.host_oracle")


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    return stages.per_batch_ms(run.trace, NAMES)
