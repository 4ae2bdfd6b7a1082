"""The production-shaped ``events`` fact table and its ``users`` dimension.

Copied from ``src/repro/data/generator.py`` (``make_events_table``,
``make_users_table``, ``gen_column``, ``_displace``) at commit 29576fc,
with the sizes ``chip_smoke.py`` (same commit) ran on the chip, so that
the data is part of the yardstick and no later change to the program's
generator moves it.  Two departures, neither of which changes a value's
distribution: categorical columns are drawn as dictionary codes rather
than as strings (the table keeps the same sorted dictionary), and a
column with clustering 0 is drawn unsorted rather than sorted and then
displaced by a full-width normal shuffle.
"""

from __future__ import annotations

import numpy as np

from . import TableData, categorical, displace

TS_MAX = 10_000_000
USER_ID_MAX = 500_000


def _int_column(rng, n, low, high, clustering):
    vals = np.sort(rng.integers(low, high, size=n))
    return displace(vals, clustering, rng).astype(np.float64)


def events(rng, partitions: int, rows_per_partition: int,
           ts_clustering: float, user_clustering: float) -> TableData:
    n = partitions * rows_per_partition
    cols, dicts = {}, {}
    cols["ts"] = _int_column(rng, n, 0, TS_MAX, ts_clustering)
    cols["user_id"] = _int_column(rng, n, 0, USER_ID_MAX, user_clustering)
    cols["score"] = rng.random(n)
    # counters correlate with ingestion order in production tables
    cols["num_sightings"] = _int_column(rng, n, 0, 100_000, 0.55)
    cols["status"], dicts["status"] = categorical(
        rng, n, ("ok", "warn", "err", "crit"), 32, 0.92)
    cols["region"], dicts["region"] = categorical(
        rng, n, ("eu", "us", "ap", "sa"), 16, 0.3)
    kinds = dict(ts="int", user_id="int", score="float", num_sightings="int",
                 status="str", region="str")
    return TableData("events", cols, kinds, dicts, rows_per_partition)


def users(rng, rows: int, rows_per_partition: int) -> TableData:
    """User ids are assigned chronologically, so age anti-correlates with
    id: a selective age predicate concentrates the build-side keys in a
    narrow id range, which the probe side's min/max can exclude."""
    ids = np.sort(rng.choice(USER_ID_MAX, size=rows, replace=False))
    age = np.clip(90.0 - ids * (70.0 / USER_ID_MAX)
                  + rng.normal(0, 4.0, rows), 10, 90).astype(np.int64)
    country, cdict = categorical(rng, rows, ("EU", "US", "AP", "SA"), 32, 0.1)
    cols = dict(id=ids.astype(np.float64), age=age.astype(np.float64),
                country=country)
    return TableData("users", cols, dict(id="int", age="int", country="str"),
                     dict(country=cdict), rows_per_partition)


def generate(cfg: dict, rng: np.random.Generator) -> dict:
    ev = events(rng, cfg["events_partitions"], cfg["events_rows_per_partition"],
                cfg["ts_clustering"], cfg["user_clustering"])
    us = users(rng, cfg["users_rows"], cfg["users_rows_per_partition"])
    return {"events": ev, "users": us}
