"""Adaptive pruning-tree (paper Sec. 3.2): reorder + cutoff invariants."""

import numpy as np
from hypothesis import given, settings

from repro.core import expr as E
from repro.core.metadata import NO_MATCH
from repro.core.prune_filter import eval_tv
from repro.core.prune_tree import AdaptivePruner

from helpers import predicates, small_tables


class TestAdaptiveTree:
    @settings(max_examples=80, deadline=None)
    @given(tbl=small_tables(), pred=predicates())
    def test_no_cutoff_matches_exact(self, tbl, pred):
        res = AdaptivePruner(pred, cutoff=False).run(tbl.stats, batch_size=3)
        np.testing.assert_array_equal(res.tv, eval_tv(pred, tbl.stats))

    @settings(max_examples=80, deadline=None)
    @given(tbl=small_tables(), pred=predicates())
    def test_cutoff_never_overprunes(self, tbl, pred):
        """Disabling a pruner may only LOSE pruning power, never gain it."""
        res = AdaptivePruner(pred, cutoff=True, scan_cost=2.0).run(
            tbl.stats, batch_size=2
        )
        exact = eval_tv(pred, tbl.stats)
        assert not ((res.tv == NO_MATCH) & (exact != NO_MATCH)).any()

    def test_reordering_reduces_work(self):
        """A cheap, highly selective filter should migrate to the front of
        the AND and short-circuit the expensive one."""
        rng = np.random.default_rng(3)
        n = 20_000
        tbl_raw = {
            "a": np.sort(rng.integers(0, 1000, size=n)),  # selective, clustered
            "b": rng.integers(0, 10, size=n),             # useless filter
        }
        from repro.data.table import Table
        tbl = Table.build("t", tbl_raw, rows_per_partition=100)
        # expensive unselective leaf FIRST in written order
        expensive = (E.col("b") * 1.0 + E.col("b") * 2.0 + E.col("b") * 3.0) >= 0.0
        selective = E.col("a") >= 995
        pred = E.And((expensive, selective))
        adaptive = AdaptivePruner(pred, reorder=True, cutoff=False)
        r1 = adaptive.run(tbl.stats, batch_size=10)
        fixed = AdaptivePruner(pred, reorder=False, cutoff=False)
        r2 = fixed.run(tbl.stats, batch_size=10)
        np.testing.assert_array_equal(r1.tv, r2.tv)
        assert r1.work_units < r2.work_units, (r1.work_units, r2.work_units)

    def test_cutoff_disables_ineffective_and_child(self):
        rng = np.random.default_rng(4)
        n = 10_000
        from repro.data.table import Table
        tbl = Table.build(
            "t",
            {"a": np.sort(rng.integers(0, 1000, size=n)),
             "b": rng.integers(0, 10, size=n)},
            rows_per_partition=100,
        )
        useless = (E.col("b") >= 0)          # never prunes anything
        selective = E.col("a") >= 900
        pruner = AdaptivePruner(E.And((useless, selective)),
                                scan_cost=5.0, cutoff=True)
        res = pruner.run(tbl.stats, batch_size=10)
        report = {r["pred"]: r for r in res.leaf_report}
        assert report[repr(useless)]["disabled"]
        assert not report[repr(selective)]["disabled"]
        # correctness preserved
        exact = eval_tv(E.And((useless, selective)), tbl.stats)
        assert not ((res.tv == NO_MATCH) & (exact != NO_MATCH)).any()

    def test_or_children_never_cut(self):
        """Paper: removing an OR child poisons the whole branch."""
        rng = np.random.default_rng(5)
        from repro.data.table import Table
        tbl = Table.build(
            "t",
            {"a": np.sort(rng.integers(0, 1000, size=5000)),
             "b": rng.integers(0, 10, size=5000)},
            rows_per_partition=50,
        )
        useless = E.col("b") >= 0
        selective = E.col("a") >= 900
        pruner = AdaptivePruner(E.Or((useless, selective)),
                                scan_cost=0.1, cutoff=True)
        res = pruner.run(tbl.stats, batch_size=10)
        assert not any(r["disabled"] for r in res.leaf_report)


# ---------------------------------------------------------------------------
# ISSUE 7 regression pins for the module docstring invariant — "with
# cutoff disabled [the adaptive tree] is bit-identical to eval_tv" — at
# the service path, and its parity with the device group pre-pass.
# ---------------------------------------------------------------------------

from hypothesis import strategies as st  # noqa: E402

from repro.core.device_stats import (  # noqa: E402
    DeviceStats, plane_capacity, tree_entry_for)
from repro.core.flow import PruningPipeline, Query, TableScanSpec  # noqa: E402
from repro.core.metadata import (  # noqa: E402
    FULL_MATCH, ColumnMeta, PartitionStats)
from repro.core.prune_tree import AdaptivePruner  # noqa: E402
from repro.kernels import ops  # noqa: E402


class TestAdaptiveServicePath:
    """The invariant through ``PruningPipeline(adaptive=True)`` itself."""

    @settings(max_examples=40, deadline=None)
    @given(tbl=small_tables(), pred=predicates())
    def test_adaptive_pipeline_sound_vs_exact_pipeline(self, pred, tbl):
        """Cutoff (enabled on the service path) may only widen the scan
        set and weaken FULL to PARTIAL — never the reverse."""
        exact = PruningPipeline().run(
            Query(scans={"t": TableScanSpec(tbl, pred)}))
        adapt = PruningPipeline(adaptive=True).run(
            Query(scans={"t": TableScanSpec(tbl, pred)}))
        e, a = exact.scan_sets["t"], adapt.scan_sets["t"]
        assert set(e.part_ids) <= set(a.part_ids), \
            "adaptive pruned a partition exact evaluation keeps"
        e_full = set(np.asarray(e.part_ids)[np.asarray(e.match)
                                            == FULL_MATCH])
        a_full = set(np.asarray(a.part_ids)[np.asarray(a.match)
                                            == FULL_MATCH])
        assert a_full <= e_full, \
            "adaptive certified FULL where exact evaluation does not"

    @settings(max_examples=40, deadline=None)
    @given(tbl=small_tables(), thresh=st.integers(-60, 60))
    def test_adaptive_pipeline_exact_on_uncuttable_predicates(self, tbl,
                                                              thresh):
        """A single-leaf predicate gives cutoff nothing to disable, so the
        service path must be bit-identical to exact evaluation — the
        docstring invariant observed end-to-end."""
        pred = E.col("x") > thresh
        exact = PruningPipeline().run(
            Query(scans={"t": TableScanSpec(tbl, pred)}))
        adapt = PruningPipeline(adaptive=True).run(
            Query(scans={"t": TableScanSpec(tbl, pred)}))
        np.testing.assert_array_equal(adapt.scan_sets["t"].part_ids,
                                      exact.scan_sets["t"].part_ids)
        np.testing.assert_array_equal(adapt.scan_sets["t"].match,
                                      exact.scan_sets["t"].match)


class TestTreePrepassOracleParity:
    """The host adaptive tree and the device group pre-pass share one
    soundness root: a hull-proven NO is final.  Property: over random
    integer stats and range workloads, the device tree path ==
    the pure-host batched oracle == per-query ``AdaptivePruner`` with
    cutoff disabled (== eval_tv by the docstring invariant)."""

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2 ** 31))
    def test_tree_kernel_matches_host_oracle_and_adaptive(self, seed):
        rng = np.random.default_rng(seed)
        P = int(rng.integers(16, 80))
        C = 2
        # integer-valued, sorted (clustered) stats: f32-exact, so the
        # staged planes agree with the f64 host oracle bit-for-bit
        mins = np.sort(rng.integers(-100, 100, (P, C)), axis=0).astype(
            np.float64)
        maxs = mins + rng.integers(0, 8, (P, C))
        stats = PartitionStats(
            columns=[ColumnMeta(f"c{i}", "int") for i in range(C)],
            mins=mins, maxs=maxs,
            null_counts=np.zeros((P, C), dtype=np.int64),
            row_counts=np.full(P, 5, dtype=np.int64))
        dstats = DeviceStats.stage(stats, capacity=plane_capacity(P))
        tree = tree_entry_for(dstats, fanout=4)
        range_lists = []
        for _ in range(int(rng.integers(1, 8))):
            k = int(rng.integers(1, 3))
            cids = rng.choice(C, size=k, replace=False)
            q = []
            for c in cids:
                lo = int(rng.integers(-120, 120))
                # narrow and keep-most widths both appear: the pre-pass
                # and its dense fallback are each exercised across seeds
                hi = lo + int(rng.integers(0, 240))
                q.append((int(c), float(lo), float(hi)))
            range_lists.append(q)
        tv_tree = ops.prune_ranges_batched_tree(range_lists, dstats, tree,
                                                mode="ref")
        tv_host = ops.prune_ranges_batched_host(range_lists, stats)
        np.testing.assert_array_equal(tv_tree, tv_host)
        for qi, ranges in enumerate(range_lists):
            pred = None
            for c, lo, hi in ranges:
                term = (E.col(f"c{c}") >= lo) & (E.col(f"c{c}") <= hi)
                pred = term if pred is None else E.And((pred, term))
            res = AdaptivePruner(pred, cutoff=False).run(
                stats, batch_size=max(P // 4, 1))
            np.testing.assert_array_equal(
                tv_tree[qi], res.tv,
                err_msg=f"q={qi}: device tree vs cutoff-free host tree")


class TestTreeLaunchShapes:
    """The tree rung's gathered launches keep one shape per level for a
    table geometry and query bucket, however many groups survive and
    however many ranges the queries carry: a shape that followed either
    would compile inside a serving window."""

    def test_survivors_and_range_counts_share_one_shape(self):
        rng = np.random.default_rng(0)
        P, C = 1024, 3
        mins = np.sort(rng.integers(0, 100_000, (P, C)), axis=0).astype(
            np.float64)
        maxs = mins + rng.integers(0, 50, (P, C))
        stats = PartitionStats(
            columns=[ColumnMeta(f"c{i}", "int") for i in range(C)],
            mins=mins, maxs=maxs,
            null_counts=np.zeros((P, C), dtype=np.int64),
            row_counts=np.full(P, 5, dtype=np.int64))
        dstats = DeviceStats.stage(stats, capacity=plane_capacity(P))
        tree = tree_entry_for(dstats, fanout=16)

        def batch(width, k):
            out = []
            for _ in range(8):
                lo = float(rng.integers(0, 100_000 - width))
                out.append([(c, lo, lo + width) for c in range(k)])
            return out

        cached = []
        for width in (100, 2_000, 8_000):
            for k in (1, 3):
                b = batch(width, k)
                tv = ops.prune_ranges_batched_tree(b, dstats, tree,
                                                   mode="ref")
                assert ops.last_tree_stats()["path"] == "tree"
                np.testing.assert_array_equal(
                    tv, ops.prune_ranges_batched_host(b, stats))
                cached.append(ops._gathered_ref_jit._cache_size())
        assert cached[-1] == cached[0]
