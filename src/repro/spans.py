"""Named host spans of the served path, on the profiler's clock.

Every stage boundary of the served path (front end -> ``run_batch`` ->
techniques -> ladder rungs -> plane getters -> kernel wrappers) opens a
``span``: a ``jax.profiler.TraceAnnotation`` named ``prune.<stage>``.  A
span records only while the profiler is tracing; otherwise it costs
about a microsecond per enter and exit.  The spans land in the same
trace as the device's ``XLA Ops`` line, so a reader can say which host
stage covered each stretch of device idle time.

Rules for placing one:

* host code only: never inside a jitted function or a Pallas kernel body;
* never inside a loop over partitions or rows: at most one span per
  stage, per table group or per query in a batch;
* metadata is small integers and short names (``q``, ``P``, ``rows``,
  ``bytes``, ``rung``).

``SPANS`` lists every name the program may open (the prefix left off).
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "prune."

SPANS = (
    # front end (serve/frontend.py)
    "frontend.prestage", "frontend.execute",
    # service (serve/prune_service.py): run_batch and its direct children
    "run_batch", "validate", "technique.filter", "technique.limit",
    "technique.join", "technique.topk", "finish", "account",
    # filter stage (PruningService.prune_batch / _verdict_group)
    "filter.plan", "filter.verdict", "filter.assemble", "filter.host_eval",
    # join and top-k stages (core/flow.py)
    "join.build_keys", "join.summarize", "join.apply", "topk.init",
    # degradation ladder (serve/resilience.py): one per rung attempt
    "rung.verdict", "rung.sharded_tree", "rung.tree", "rung.sharded",
    "rung.device", "rung.host_kernel", "rung.host_oracle",
    "rung.passthrough", "backoff",
    # LIMIT stage (core/flow.py): choosing the fully-matching partitions
    # and the cut, once per LIMIT query
    "limit.select",
    # resident planes (core/device_stats.py); planes.limbs: the split of
    # wide columns' stats into exact limbs, at stage and at replay
    "planes.stage", "planes.verify", "planes.limbs",
    # batched kernel wrappers (kernels/ops.py): once per launch
    "pack", "dispatch", "device_wait", "d2h", "unpack",
)


def span(name: str, **meta) -> TraceAnnotation:
    """The host span ``prune.<name>``; use as a context manager."""
    return TraceAnnotation(PREFIX + name, **meta)
