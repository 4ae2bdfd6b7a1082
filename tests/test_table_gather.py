"""The row gather of a partition list (``Table.ctx_for``).

Given ``columns``, the gather reads only those columns and their null
masks, slice by run of consecutive partition ids: one run is a read-only
view of the table, several runs one concatenation per column.  It must
equal the full gather bit for bit, in the order of the ids, and a join's
build scan must read no other column of the build table.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program, trafficgen  # noqa: E402
from bench.tables import tpch  # noqa: E402
from repro.core.flow import JoinTechnique, PruneState, PruningPipeline  # noqa: E402
from repro.core.metadata import ScanSet  # noqa: E402
from repro.core.rowval import matches  # noqa: E402
from repro.data.table import Table  # noqa: E402

def small_table() -> Table:
    """12 partitions of 5 rows and a last one of 2; ``b`` has nulls."""
    rng = np.random.default_rng(3)
    n = 62
    raw = dict(a=rng.integers(0, 1000, n), b=rng.normal(size=n),
               c=rng.choice(["x", "y", "z"], n), d=np.arange(n))
    return Table.build("t", raw, rows_per_partition=5,
                       nulls=dict(b=rng.random(n) < 0.3))


def _rewrite(t: Table):
    rows = int(np.diff(t.part_bounds)[[2, 3]].sum())
    t.rewrite_partitions([2, 3], dict(
        a=np.full(rows, 7), b=np.linspace(0, 1, rows),
        c=np.array(["z"] * rows), d=np.arange(rows) + 500),
        nulls=dict(b=np.arange(rows) % 2 == 0))
    return [1, 2, 3, 4]


def _drop(t: Table):
    t.drop_partitions([4, 5])
    return list(np.flatnonzero(t.live_mask))


CASES = {
    "one_run": lambda t: [3, 4, 5, 6],
    "whole_table": lambda t: list(range(t.num_partitions)),
    "several_runs": lambda t: [0, 1, 2, 7, 8, 12],
    "scattered": lambda t: [1, 3, 5, 9],
    "out_of_order": lambda t: [8, 9, 2, 3, 4, 0],
    "empty": lambda t: [],
    "after_drop": _drop,
    "after_rewrite": _rewrite,
}


def _same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


@pytest.mark.parametrize("cols", [("b",), ("a", "c"), ("d", "b", "a"),
                                  ("a", "b", "a")],
                         ids=lambda c: "+".join(c))
@pytest.mark.parametrize("case", list(CASES))
def test_a_column_gather_by_runs_equals_the_full_gather(case, cols):
    t = small_table()
    ids = CASES[case](t)
    before = {k: v.copy() for k, v in t.data.items()}
    nulls_before = {k: v.copy() for k, v in t.nulls.items()}
    full = t.ctx_for(ids)
    got = t.ctx_for(ids, columns=cols)
    assert set(got.data) == set(cols)
    assert set(got.nulls) == {c for c in cols if c in t.nulls}
    assert got.n == full.n
    for c in cols:
        assert _same_bits(got.data[c], full.data[c]), c
        assert _same_bits(got.col(c)[1], full.col(c)[1]), c
    # the gather leaves the table as it found it
    for k in t.data:
        assert _same_bits(t.data[k], before[k]), k
    for k in t.nulls:
        assert _same_bits(t.nulls[k], nulls_before[k]), k
    runs = len(t.row_runs(ids)[0])
    copied = sum(a.nbytes for a in [*got.data.values(), *got.nulls.values()])
    assert t.scan_meta(ids, cols) == dict(
        cols=len(set(cols)), runs=runs, rows=full.n,
        bytes=0 if runs == 1 else copied)
    for arr in [*got.data.values(), *got.nulls.values()]:
        if runs == 1:          # a view of the table, which it cannot write
            assert not arr.flags.writeable
            with pytest.raises(ValueError):
                arr[:1] = 0
        else:                  # a copy of its own
            assert not any(np.shares_memory(arr, v)
                           for v in [*t.data.values(), *t.nulls.values()])


@pytest.mark.parametrize("ids,expect", [
    ([], []),
    ([4], [(4, 4)]),
    ([0, 1, 2, 3], [(0, 3)]),
    ([3, 4, 9, 10, 11, 1], [(3, 4), (9, 11), (1, 1)]),
    ([5, 5, 6], [(5, 5), (5, 6)]),
    ([6, 5], [(6, 6), (5, 5)]),
])
def test_row_runs_are_the_maximal_runs_of_consecutive_ids(ids, expect):
    t = small_table()
    lo, hi = t.row_runs(ids)
    b = t.part_bounds
    assert [(int(x), int(y)) for x, y in zip(lo, hi)] == \
        [(int(b[p]), int(b[q + 1])) for p, q in expect]


# -- a join's build scan on the TPC-H orders table ---------------------------

TEMPLATES = ("Q3", "Q4", "Q5", "Q8", "Q10", "Q21")


class Recording(dict):
    """A ``Table.data`` that remembers which columns were read."""

    def __init__(self, data):
        super().__init__(data)
        self.read = set()

    def __getitem__(self, k):
        self.read.add(k)
        return super().__getitem__(k)

    def get(self, k, default=None):
        self.read.add(k)
        return super().get(k, default)

    def items(self):
        self.read.update(self.keys())
        return super().items()

    def values(self):
        self.read.update(self.keys())
        return super().values()


@pytest.fixture(scope="module")
def tpch_tables():
    rng = np.random.default_rng(19)
    return {"orders": program.build_table(tpch.orders(rng, 1, 64, 64)),
            "lineitem": program.build_table(tpch.lineitem(rng, 1, 16, 64))}


def _split(ids: np.ndarray) -> np.ndarray:
    """The filtered set with its middle third left out: two runs."""
    k = len(ids) // 3
    return np.concatenate([ids[:k], ids[2 * k:]])


@pytest.mark.parametrize("scan", ["filtered", "split", "full"])
@pytest.mark.parametrize("template", TEMPLATES)
def test_build_keys_equal_the_full_gather_and_read_only_their_columns(
        tpch_tables, template, scan):
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                      "streams-batch.json")))
    plain = trafficgen.draw_query(template, mix["templates"][template],
                                  trafficgen.rng_for(2 ** 33 + 19, 0))
    q = program.to_query(plain, tpch_tables)
    orders = tpch_tables["orders"]
    ids = PruningPipeline(filter_mode="host").run(q) \
        .scan_sets["orders"].part_ids
    ids = {"filtered": ids, "split": _split(ids),
           "full": np.arange(orders.num_partitions)}[scan]
    # orders is sorted on its date, and each build predicate reads the
    # date or the status that follows it: the filtered set is one run
    runs = {"filtered": 1, "split": 2, "full": 1}[scan]
    assert len(orders.row_runs(ids)[0]) == runs
    pred, key = q.scans["orders"].pred, q.join.build_key

    ctx = orders.ctx_for(ids)
    vals, knulls = ctx.col(key)
    expect = vals[matches(pred, ctx) & ~knulls]

    data = orders.data
    orders.data = Recording(data)
    try:
        got = JoinTechnique()._build_keys(PruneState(
            q, scan_sets={"orders": ScanSet(ids)}))
        read = orders.data.read
    finally:
        orders.data = data
    assert _same_bits(got, expect)
    assert len(got) > 0
    assert read == set(pred.columns()) | {key}
