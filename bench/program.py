"""The seam to the system under test: engine tables, queries and reports.

Everything the benchmark hands the program passes through here: the
generated columns become engine ``Table``s, plain queries become engine
``Query``s, and reports come back as ``reference.Outcome``s with the
per-request failure flags the run counts.
"""

from __future__ import annotations

import numpy as np

from .reference import Outcome


# The program is imported inside each function: ``bench.run`` fixes the
# compile cache and checks for the chip before anything touches JAX.


def build_table(td):
    """An engine table over the generated (already encoded) columns."""
    from repro.core.metadata import ColumnMeta, PartitionStats
    from repro.data.table import Table
    metas = {c: ColumnMeta(c, td.kinds[c], td.dictionaries.get(c))
             for c in td.columns}
    bounds = td.part_bounds
    stats = PartitionStats.from_columns(list(metas.values()), td.columns, {},
                                        bounds)
    return Table(td.name, metas, td.columns, {}, bounds, stats)


def to_pred(pred: list):
    from repro.core import expr as E
    op = pred[0]
    if op == "true":
        return E.true()
    if op == "and":
        return E.And(tuple(to_pred(p) for p in pred[1:]))
    if op == "or":
        return E.Or(tuple(to_pred(p) for p in pred[1:]))
    if op == "cmp":
        return E.Cmp(pred[2], E.col(pred[1]), E.lit(pred[3]))
    if op == "colcmp":
        return E.Cmp(pred[2], E.col(pred[1]), E.col(pred[3]))
    if op == "in":
        return E.in_(E.col(pred[1]), pred[2])
    raise ValueError(f"unknown predicate node {op!r}")


def to_query(q: dict, tables: dict):
    from repro.core.flow import JoinSpec, Query, TableScanSpec
    scans = {t: TableScanSpec(tables[t], to_pred(p))
             for t, p in q["scans"].items()}
    join = None
    if q["join"]:
        j = q["join"]
        join = JoinSpec(j["build"], j["probe"], j["build_key"], j["probe_key"])
    order_by = tuple(q["order_by"]) if q["order_by"] else None
    return Query(scans=scans, join=join, limit=q["limit"], order_by=order_by)


def outcome(report) -> Outcome:
    sets = {n: (np.asarray(s.part_ids), np.asarray(s.match))
            for n, s in report.scan_sets.items()}
    out = Outcome(sets)
    if report.topk is not None:
        out.topk_values = np.asarray(report.topk.values)
        out.topk_skipped = np.asarray(report.topk.skipped)
    return out


def failed(report) -> bool:
    """A report that did not come from the engine's pruning: the no-prune
    passthrough, an isolated error, or any report of a salvaged batch or
    of a batch in which a launch fell to the passthrough rung.  A host
    fallback is exact and is not a failure."""
    res = (report.counters or {}).get("resilience", {})
    if res.get("salvaged_batches") or res.get("passthroughs"):
        return True
    return any(r.detail.get("path") == "passthrough"
               for techs in report.per_scan.values()
               for r in techs.values())


def counter_delta(svc, before: dict) -> dict:
    """Fallbacks, demotions and the rest since ``before``
    (a ``snapshot(svc)``)."""
    from repro.serve.prune_service import ServiceCounters
    from repro.serve.resilience import resilience_delta
    now = snapshot(svc)
    return dict(counters=ServiceCounters.delta(before["counters"],
                                               now["counters"]),
                resilience=resilience_delta(before["resilience"],
                                            now["resilience"]))


def snapshot(svc) -> dict:
    from repro.serve.resilience import resilience_snapshot
    return dict(counters=svc.counters.snapshot(),
                resilience=resilience_snapshot(svc.resilience))
