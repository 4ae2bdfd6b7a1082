"""Filter pruning (paper Sec. 3): soundness, paper examples, fast path."""

import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import expr as E
from repro.core.metadata import (FULL_MATCH, NO_MATCH, PARTIAL_MATCH,
                                 ColumnMeta, PartitionStats)
from repro.core.prune_filter import (eval_ranges_tv, eval_tv, extract_ranges,
                                     fully_matching_two_pass)
from repro.core.rowval import matches
from repro.data.table import Table

from helpers import arith_pred, predicates, small_tables


def fig5_table() -> Table:
    """The paper's Figure 5: 4 micro-partitions of tracking data."""
    species = (
        ["Duck", "Eagle", "Frog", "Pike"] * 2              # p0: no Alpine
        + ["Alpine Ibex", "Alpine Marmot"] * 4             # p1: all Alpine, s>=50
        + ["Alpine Ibex", "Duck", "Alpine Marmot", "Pike"] * 2   # p2: mixed
        + ["Alpine Ibex", "Bear", "Alpine Chough", "Wolf"] * 2   # p3: mixed
    )
    s = ([40, 75, 8, 60] * 2
         + [85, 50, 86, 51, 87, 52, 88, 53]
         + [90, 18, 55, 12] * 2
         + [95, 170, 58, 120] * 2)
    return Table.build(
        "tracking_data",
        {"species": np.array(species), "s": np.array(s, dtype=np.int64)},
        rows_per_partition=8,
    )


PRED_FIG5 = E.like(E.col("species"), "Alpine%") & (E.col("s") >= 50)


class TestPaperExamples:
    def test_fig5_three_classes(self):
        tbl = fig5_table()
        tv = eval_tv(PRED_FIG5, tbl.stats)
        assert tv[0] == NO_MATCH          # pruned: no Alpine species
        assert tv[1] == FULL_MATCH        # fully matching (Fig. 5's p3)
        assert tv[2] == PARTIAL_MATCH
        assert tv[3] == PARTIAL_MATCH

    def test_fig5_two_pass_equivalence(self):
        tbl = fig5_table()
        tv = eval_tv(PRED_FIG5, tbl.stats)
        fm = fully_matching_two_pass(PRED_FIG5, tbl.stats)
        np.testing.assert_array_equal(fm, tv == FULL_MATCH)

    def test_sec31_if_expression_not_pruned(self):
        """The guiding query's partition must be retained (paper metadata:
        unit in [feet, meters], altit in [934, 7674])."""
        tbl = Table.build(
            "trails",
            {
                "unit": np.array(["feet", "meters"] * 50),
                "altit": np.linspace(934, 7674, 100),
                "name": np.array(["Marked-A-Ridge", "Basecamp"] * 50),
            },
            rows_per_partition=100,
        )
        pred = (
            E.if_(E.col("unit") == E.lit("feet"),
                  E.col("altit") * 0.3048, E.col("altit")) > 1500
        ) & E.like(E.col("name"), "Marked-%-Ridge")
        assert eval_tv(pred, tbl.stats)[0] == PARTIAL_MATCH

    def test_sec31_if_expression_prunes_feet_partition(self):
        """A partition that is all-'feet' with low altitude IS prunable:
        the IF range collapses to the feet branch (934*0.3048 < 1500)."""
        tbl = Table.build(
            "trails",
            {
                "unit": np.array(["feet"] * 50 + ["meters"] * 50),
                "altit": np.concatenate([
                    np.linspace(934, 4000, 50),   # feet: max 4000*0.3048=1219m
                    np.linspace(100, 1200, 50),   # meters: max 1200 < 1500
                ]),
            },
            rows_per_partition=50,
        )
        pred = E.if_(E.col("unit") == E.lit("feet"),
                     E.col("altit") * 0.3048, E.col("altit")) > 1500
        tv = eval_tv(pred, tbl.stats)
        assert tv[0] == NO_MATCH   # all feet, converted max < 1500
        assert tv[1] == NO_MATCH   # all meters, max < 1500

    def test_imprecise_rewrite_never_full(self):
        """'Marked-%-Ridge' is widened: it may prune but never certify."""
        tbl = Table.build(
            "t", {"name": np.array(["Marked-A-Ridge", "Marked-B-Ridge"] * 4)},
            rows_per_partition=8,
        )
        tv = eval_tv(E.like(E.col("name"), "Marked-%-Ridge"), tbl.stats)
        assert tv[0] == PARTIAL_MATCH  # truly all-matching, but unprovable
        tv2 = eval_tv(E.like(E.col("name"), "Marked-%"), tbl.stats)
        assert tv2[0] == FULL_MATCH    # trailing-% rewrite is exact


class TestSoundness:
    @settings(max_examples=120, deadline=None)
    @given(tbl=small_tables(), pred=predicates())
    def test_no_false_negatives_and_full_is_full(self, tbl, pred):
        """THE invariant: NO => no row matches; FULL => every row matches."""
        tv = eval_tv(pred, tbl.stats)
        for p in range(tbl.num_partitions):
            m = matches(pred, tbl.partition_ctx(p))
            if tv[p] == NO_MATCH:
                assert not m.any(), f"false negative in partition {p}: {pred!r}"
            elif tv[p] == FULL_MATCH:
                assert m.all(), f"bogus FULL in partition {p}: {pred!r}"

    @settings(max_examples=120, deadline=None)
    @given(tbl=small_tables(with_nulls=False), pred=predicates())
    def test_one_pass_equals_two_pass_without_nulls(self, tbl, pred):
        """DESIGN.md §6.1: on null-free data the lattice FULL equals the
        paper's inverted-predicate second pass exactly."""
        tv = eval_tv(pred, tbl.stats)
        fm = fully_matching_two_pass(pred, tbl.stats)
        np.testing.assert_array_equal(fm, tv == FULL_MATCH)

    @settings(max_examples=120, deadline=None)
    @given(tbl=small_tables(with_nulls=True), pred=predicates())
    def test_one_pass_dominates_two_pass_with_nulls(self, tbl, pred):
        """With NULLs the lattice is strictly STRONGER: the two-pass method
        needs a global null guard (see prune_filter.fully_matching_two_pass)
        which loses cases like OR(p_nullcol, q_full) where q alone certifies
        every row.  One-pass FULL must be a superset — and still sound,
        which test_no_false_negatives_and_full_is_full guarantees."""
        tv = eval_tv(pred, tbl.stats)
        fm = fully_matching_two_pass(pred, tbl.stats)
        assert (~fm | (tv == FULL_MATCH)).all()  # two_pass => one_pass

    @settings(max_examples=80, deadline=None)
    @given(tbl=small_tables())
    def test_complex_arithmetic_soundness(self, tbl):
        pred = arith_pred(30.0)
        tv = eval_tv(pred, tbl.stats)
        for p in range(tbl.num_partitions):
            m = matches(pred, tbl.partition_ctx(p))
            if tv[p] == NO_MATCH:
                assert not m.any()
            elif tv[p] == FULL_MATCH:
                assert m.all()


class TestRangeFastPath:
    def test_extract_simple_conjunction(self):
        tbl = fig5_table()
        pred = E.startswith(E.col("species"), "Alpine") & (E.col("s") >= 50)
        ranges = extract_ranges(pred, tbl.stats)
        assert ranges is not None and len(ranges) == 2
        np.testing.assert_array_equal(
            eval_ranges_tv(ranges, tbl.stats), eval_tv(pred, tbl.stats)
        )

    def test_like_trailing_percent_extracts(self):
        tbl = fig5_table()
        ranges = extract_ranges(PRED_FIG5, tbl.stats)
        assert ranges is not None
        np.testing.assert_array_equal(
            eval_ranges_tv(ranges, tbl.stats), eval_tv(PRED_FIG5, tbl.stats)
        )

    def test_disjunction_rejected(self):
        tbl = fig5_table()
        pred = (E.col("s") > 10) | (E.col("s") < 5)
        assert extract_ranges(pred, tbl.stats) is None

    @settings(max_examples=60, deadline=None)
    @given(tbl=small_tables(with_nulls=True))
    def test_fast_path_matches_general(self, tbl):
        pred = (E.col("x") >= -10) & (E.col("x") < 25) & (E.col("y") > 100)
        ranges = extract_ranges(pred, tbl.stats)
        assert ranges is not None
        np.testing.assert_array_equal(
            eval_ranges_tv(ranges, tbl.stats), eval_tv(pred, tbl.stats)
        )


class TestNullSemantics:
    def test_all_null_partition_prunes(self):
        tbl = Table.build(
            "t", {"x": np.arange(8, dtype=np.int64)},
            rows_per_partition=4,
            nulls={"x": np.array([True] * 4 + [False] * 4)},
        )
        tv = eval_tv(E.col("x") >= 0, tbl.stats)
        assert tv[0] == NO_MATCH     # all-null partition: nothing matches
        assert tv[1] == FULL_MATCH

    def test_nulls_block_full(self):
        tbl = Table.build(
            "t", {"x": np.arange(8, dtype=np.int64)},
            rows_per_partition=8,
            nulls={"x": np.array([True] + [False] * 7)},
        )
        tv = eval_tv(E.col("x") >= 0, tbl.stats)
        assert tv[0] == PARTIAL_MATCH  # one null row fails the predicate

    def test_not_with_nulls_is_conservative(self):
        tbl = Table.build(
            "t", {"x": np.full(8, 5, dtype=np.int64)},
            rows_per_partition=8,
            nulls={"x": np.array([True] * 4 + [False] * 4)},
        )
        tv = eval_tv(E.Not(E.col("x") > 10), tbl.stats)
        assert tv[0] == PARTIAL_MATCH  # nulls satisfy neither branch

    def test_is_null_three_way(self):
        tbl = Table.build(
            "t", {"x": np.arange(12, dtype=np.int64)},
            rows_per_partition=4,
            nulls={"x": np.array([True] * 4 + [False] * 4 + [True, False] * 2)},
        )
        tv = eval_tv(E.is_null(E.col("x")), tbl.stats)
        np.testing.assert_array_equal(tv, [FULL_MATCH, NO_MATCH, PARTIAL_MATCH])
        tv = eval_tv(E.is_not_null(E.col("x")), tbl.stats)
        np.testing.assert_array_equal(tv, [NO_MATCH, FULL_MATCH, PARTIAL_MATCH])


def _loop_stats(columns, encoded, null_masks, part_bounds):
    """Partition-at-a-time reference for PartitionStats.from_columns."""
    P, C = len(part_bounds) - 1, len(columns)
    mins = np.full((P, C), np.inf)
    maxs = np.full((P, C), -np.inf)
    nulls = np.zeros((P, C), dtype=np.int64)
    for ci, col in enumerate(columns):
        for p in range(P):
            s, e = part_bounds[p], part_bounds[p + 1]
            v = encoded[col.name][s:e]
            m = null_masks.get(col.name)
            if m is not None:
                nulls[p, ci] = int(m[s:e].sum())
                v = v[~m[s:e]]
            if v.size:
                mins[p, ci], maxs[p, ci] = v.min(), v.max()
    return mins, maxs, nulls


class TestPartitionStatsBuild:
    @pytest.mark.parametrize("bounds,null_frac", [
        ([0, 8, 16, 24], 0.0),
        ([0, 3, 3, 10, 10, 10, 24], 0.3),      # empty partitions
        ([0, 1, 2, 24], 0.5),
        ([0, 24], 1.0),                        # an all-null partition
    ])
    def test_segmented_reduction_matches_partition_loop(self, bounds,
                                                        null_frac):
        rng = np.random.default_rng(len(bounds))
        cols = [ColumnMeta("a", "int"), ColumnMeta("b", "float")]
        enc = {"a": rng.integers(-50, 50, 24).astype(np.float64),
               "b": rng.uniform(-1, 1, 24)}
        masks = {"b": rng.random(24) < null_frac} if null_frac else {}
        bounds = np.asarray(bounds, dtype=np.int64)
        st = PartitionStats.from_columns(cols, enc, masks, bounds)
        mins, maxs, nulls = _loop_stats(cols, enc, masks, bounds)
        np.testing.assert_array_equal(st.mins, mins)
        np.testing.assert_array_equal(st.maxs, maxs)
        np.testing.assert_array_equal(st.null_counts, nulls)
        np.testing.assert_array_equal(st.row_counts, np.diff(bounds))
