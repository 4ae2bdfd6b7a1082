"""The ``events`` fact table as an Apache Iceberg table holds it: ``ts``
is an Iceberg ``timestamp``, a ``long`` of microseconds since the epoch,
and so are its manifest lower and upper bounds (Iceberg table spec,
"Primitive types" and "Appendix D: Single-value serialization").

The same six columns, clustering and sizes as ``bench/tables/events.py``,
whose helpers draw them; only ``ts`` differs: uniform over the configured
window (2024), clustered as ``events-1m``'s.  Every ``ts`` value is a
whole number near 1.7e15, exact in float64 (below 2**53) and far beyond
float32's 24-bit mantissa.  No ``users`` table: the deployment's queries
read ``events`` alone.
"""

from __future__ import annotations

import numpy as np

from . import TableData, categorical
from .events import USER_ID_MAX, _int_column


def events(rng, partitions: int, rows_per_partition: int, ts_start: int,
           ts_span: int, ts_clustering: float,
           user_clustering: float) -> TableData:
    n = partitions * rows_per_partition
    cols, dicts = {}, {}
    cols["ts"] = _int_column(rng, n, ts_start, ts_start + ts_span,
                             ts_clustering)
    cols["user_id"] = _int_column(rng, n, 0, USER_ID_MAX, user_clustering)
    cols["score"] = rng.random(n)
    cols["num_sightings"] = _int_column(rng, n, 0, 100_000, 0.55)
    cols["status"], dicts["status"] = categorical(
        rng, n, ("ok", "warn", "err", "crit"), 32, 0.92)
    cols["region"], dicts["region"] = categorical(
        rng, n, ("eu", "us", "ap", "sa"), 16, 0.3)
    kinds = dict(ts="int", user_id="int", score="float", num_sightings="int",
                 status="str", region="str")
    return TableData("events", cols, kinds, dicts, rows_per_partition)


def generate(cfg: dict, rng: np.random.Generator) -> dict:
    return {"events": events(
        rng, cfg["events_partitions"], cfg["events_rows_per_partition"],
        cfg["ts_start_us"], cfg["ts_span_us"], cfg["ts_clustering"],
        cfg["user_clustering"])}
