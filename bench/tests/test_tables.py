"""The generated data has the shapes its configuration claims."""

import numpy as np
import pytest

from bench.tests.common import small_tables

pytestmark = pytest.mark.filterwarnings("ignore")


def _spans(td, col):
    starts = td.part_bounds[:-1]
    v = td.columns[col]
    return np.minimum.reduceat(v, starts), np.maximum.reduceat(v, starts)


def test_tpch_keeps_the_specification_shapes():
    from bench.tables import tpch
    data = small_tables("tpch-sf1000")
    li, od = data["lineitem"], data["orders"]
    assert li.rows_per_partition == od.rows_per_partition == 1024
    # decimals and dates as scaled integers: every value whole
    for td in (li, od):
        for c, v in td.columns.items():
            assert np.array_equal(v, np.round(v)), c
    assert li.columns["l_discount"].min() == 0 and li.columns["l_discount"].max() == 10
    assert li.columns["l_tax"].max() == 8
    # clustered on the dates: partitions follow each other without overlap
    for td, c in ((li, "l_shipdate"), (od, "o_orderdate")):
        lo, hi = _spans(td, c)
        assert np.all(hi[:-1] <= lo[1:])
    # unclustered columns span their whole domain in (nearly) every
    # partition, as in a 732k-row one
    for c, lo_v, hi_v in (("l_quantity", 1, 50), ("l_discount", 0, 10),
                          ("l_shipmode", 0, 6), ("l_shipinstruct", 0, 3)):
        lo, hi = _spans(li, c)
        assert np.mean((lo == lo_v) & (hi == hi_v)) > 0.99, c
    # order keys drawn apart from the dates: each partition spans nearly
    # the whole 6e9 key domain
    for td, c in ((li, "l_orderkey"), (od, "o_orderkey")):
        lo, hi = _spans(td, c)
        assert np.median((hi - lo) / 6e9) > 0.99
    # flags follow the dates as dbgen derives them
    rcpt = li.columns["l_receiptdate"]
    n_code = li.encode("l_returnflag", "N")
    assert np.array_equal(li.columns["l_returnflag"] == n_code,
                          rcpt > tpch.CURRENTDATE)
    assert np.all(li.columns["l_receiptdate"] > li.columns["l_shipdate"])


def test_events_are_time_clustered_with_narrow_user_spans():
    data = small_tables("events-1m")
    ev = data["events"]
    assert ev.rows_per_partition == 8
    lo, hi = _spans(ev, "ts")
    assert np.median(hi - lo) < 10_000_000 / ev.num_partitions * 20
    users = data["users"]
    age = users.columns["age"]
    assert np.corrcoef(users.columns["id"], age)[0, 1] < -0.9
