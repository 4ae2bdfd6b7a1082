"""TPC-H ``lineitem`` and ``orders``, clustered on their dates.

Replaces ``make_lineitem`` / ``make_orders`` of ``src/repro/data/generator.py``
(commit 29576fc), which draw 1,000 rows per partition, decimals as floats
and an order key that follows the dates (clustering 0.97).  This module
keeps the TPC-H specification's own shapes (section 4.2.3, dbgen):

* dates as days since 1970-01-01: ``o_orderdate`` uniform in
  [1992-01-01, 1998-08-02], ``l_shipdate = orderdate + [1, 121]``,
  ``l_commitdate = orderdate + [30, 90]``,
  ``l_receiptdate = shipdate + [1, 30]``;
* decimals as scaled integers (``NUMBER(p, 2)`` is fixed point): prices in
  cents, discount and tax in hundredths;
* flags, modes and priorities as dictionary codes, with the flags derived
  from the dates as dbgen derives them;
* keys drawn independently of the dates, as dbgen draws ``o_orderdate``
  per order at random: a date-clustered partition spans nearly the whole
  key domain.  At SF 1000 order keys reach 6e9.

Each table is sorted on its date (``CLUSTER BY l_shipdate`` /
``o_orderdate``), then cut into equal partitions.  Free-text columns
(comments, names, clerks) are held as uniform codes: they are never
predicated, and each keeps its column of stat planes.  Lineitem rows are
drawn independently of the orders rows: no query here reads a row of one
through the other, only key ranges.
"""

from __future__ import annotations

import datetime

import numpy as np

from . import TableData

EPOCH = datetime.date(1970, 1, 1)


def day(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


STARTDATE = day("1992-01-01")
CURRENTDATE = day("1995-06-17")
ENDDATE = day("1998-12-31")

RETURNFLAGS = ("A", "N", "R")
LINESTATUS = ("F", "O")
SHIPINSTRUCT = ("COLLECT COD", "DELIVER IN PERSON", "NONE",
                "TAKE BACK RETURN")
SHIPMODES = ("AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK")
ORDERSTATUS = ("F", "O", "P")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")


def _codes(values):
    return np.array(sorted(values))


def _sort_by(cols: dict, key: str) -> dict:
    order = np.argsort(cols[key], kind="stable")
    return {k: v[order].astype(np.float64) for k, v in cols.items()}


def lineitem(rng, sf: int, partitions: int, rows_per_partition: int
             ) -> TableData:
    n = partitions * rows_per_partition
    odate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n)
    ship = odate + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    qty = rng.integers(1, 51, n)
    retail = rng.integers(90_000, 209_901, n)             # cents
    returned = receipt <= CURRENTDATE
    flag = np.where(returned, rng.choice([0, 2], n), 1)   # A|R, else N
    cols = dict(
        l_orderkey=rng.integers(1, 6_000_000 * sf + 1, n),
        l_partkey=rng.integers(1, 200_000 * sf + 1, n),
        l_suppkey=rng.integers(1, 10_000 * sf + 1, n),
        l_linenumber=rng.integers(1, 8, n),
        l_quantity=qty,
        l_extendedprice=qty * retail,
        l_discount=rng.integers(0, 11, n),                # hundredths
        l_tax=rng.integers(0, 9, n),
        l_returnflag=flag,
        l_linestatus=(ship > CURRENTDATE).astype(np.int64),   # F=0, O=1
        l_shipdate=ship,
        l_commitdate=odate + rng.integers(30, 91, n),
        l_receiptdate=receipt,
        l_shipinstruct=rng.integers(0, len(SHIPINSTRUCT), n),
        l_shipmode=rng.integers(0, len(SHIPMODES), n),
        l_comment=rng.integers(0, 1 << 30, n),
    )
    kinds = {k: "int" for k in cols}
    dicts = dict(l_returnflag=_codes(RETURNFLAGS),
                 l_linestatus=_codes(LINESTATUS),
                 l_shipinstruct=_codes(SHIPINSTRUCT),
                 l_shipmode=_codes(SHIPMODES))
    kinds.update({k: "str" for k in dicts})
    return TableData("lineitem", _sort_by(cols, "l_shipdate"), kinds, dicts,
                     rows_per_partition)


def orders(rng, sf: int, partitions: int, rows_per_partition: int
           ) -> TableData:
    n = partitions * rows_per_partition
    odate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n)
    # every line ships within [1, 121] days: all shipped by CURRENTDATE
    # is F, none shipped is O, some is P
    status = np.where(odate + 121 <= CURRENTDATE, 0,
                      np.where(odate + 1 > CURRENTDATE, 1, 2))
    cols = dict(
        o_orderkey=rng.integers(1, 6_000_000 * sf + 1, n),
        o_custkey=rng.integers(1, 150_000 * sf + 1, n),
        o_orderstatus=status,
        o_totalprice=rng.integers(85_000, 55_500_000, n),  # cents
        o_orderdate=odate,
        o_orderpriority=rng.integers(0, len(PRIORITIES), n),
        o_clerk=rng.integers(1, 1_000 * sf + 1, n),
        o_shippriority=np.zeros(n, dtype=np.int64),
        o_comment=rng.integers(0, 1 << 30, n),
    )
    kinds = {k: "int" for k in cols}
    dicts = dict(o_orderstatus=_codes(ORDERSTATUS),
                 o_orderpriority=_codes(PRIORITIES))
    kinds.update({k: "str" for k in dicts})
    return TableData("orders", _sort_by(cols, "o_orderdate"), kinds, dicts,
                     rows_per_partition)


def generate(cfg: dict, rng: np.random.Generator) -> dict:
    sf = cfg["scale_factor"]
    li = lineitem(rng, sf, cfg["lineitem_partitions"],
                  cfg["lineitem_rows_per_partition"])
    od = orders(rng, sf, cfg["orders_partitions"],
                cfg["orders_rows_per_partition"])
    return {"lineitem": li, "orders": od}
