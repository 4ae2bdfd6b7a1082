"""JOIN pruning (paper Sec. 6): probabilistic but never incorrect."""

import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.metadata import ScanSet
from repro.core.prune_join import (BlockedBloom, prune_probe, summarize_build)
from repro.data.table import Table


class TestBlockedBloom:
    @settings(max_examples=60, deadline=None)
    @given(keys=st.lists(st.integers(-2**40, 2**40), min_size=1, max_size=300))
    def test_no_false_negatives(self, keys):
        keys = np.asarray(keys, dtype=np.int64)
        bloom = BlockedBloom(len(keys))
        bloom.add(keys)
        assert bloom.contains(keys).all()

    def test_false_positive_rate_reasonable(self):
        rng = np.random.default_rng(0)
        keys = rng.choice(2**40, size=10_000, replace=False)
        bloom = BlockedBloom(len(keys), bits_per_key=16)
        bloom.add(keys)
        probe = rng.choice(2**40, size=50_000, replace=False)
        probe = probe[~np.isin(probe, keys)]
        fpr = bloom.contains(probe).mean()
        assert fpr < 0.01, f"blocked bloom fpr {fpr:.4f} too high"

    def test_size_bounded(self):
        bloom = BlockedBloom(100_000, bits_per_key=16)
        assert bloom.size_bytes <= 100_000 * 4  # ~2 bytes/key at 16 bits


class TestBuildSummary:
    def test_small_ndv_uses_distinct(self):
        s = summarize_build(np.array([1, 2, 3, 2, 1]), ndv_limit=10)
        assert s.distinct is not None and s.bloom is None
        assert s.min == 1 and s.max == 3

    def test_large_ndv_uses_bloom(self):
        s = summarize_build(np.arange(10_000), ndv_limit=100)
        assert s.bloom is not None and s.distinct is None
        # summary stays a small fraction of the build side (Sec. 6.1)
        assert s.size_bytes < 10_000 * 8 * 0.5

    def test_nulls_excluded(self):
        s = summarize_build(np.array([1, 2, 3]), null_mask=np.array([False, True, False]))
        assert s.count == 2 and s.max == 3

    def test_empty_build_distinct_keeps_key_dtype(self):
        """Regression: the empty distinct set used to be a float64
        np.zeros(0) regardless of the key domain."""
        s = summarize_build(np.zeros(0, dtype=np.int64))
        assert s.empty and s.distinct.dtype == np.int64
        s = summarize_build(np.array([1, 2]), null_mask=np.array([True, True]))
        assert s.empty and s.distinct.dtype == np.int64


def _probe_table(vals, rows_per_partition=4):
    return Table.build("probe", {"k": np.asarray(vals, dtype=np.int64)},
                       rows_per_partition=rows_per_partition)


class TestProbePruning:
    def test_range_pruning(self):
        tbl = _probe_table(np.arange(40))          # partitions of 4: [0..3],[4..7]...
        summary = summarize_build(np.array([9, 10, 11]))
        res = prune_probe(ScanSet.full(tbl.num_partitions), tbl.stats, "k", summary)
        kept = set(res.scan.part_ids.tolist())
        assert kept == {2}  # only partition [8..11] overlaps
        assert res.pruned_by_range + res.pruned_by_distinct == 9

    def test_distinct_pruning_beats_range(self):
        # build keys {0, 39}: range overlap keeps everything, distinct kills middle
        tbl = _probe_table(np.arange(40))
        summary = summarize_build(np.array([0, 39]))
        res = prune_probe(ScanSet.full(tbl.num_partitions), tbl.stats, "k", summary)
        kept = set(res.scan.part_ids.tolist())
        assert kept == {0, 9}
        assert res.pruned_by_distinct == 8

    def test_bloom_pruning_narrow_partitions(self):
        rng = np.random.default_rng(1)
        build = rng.choice(1_000_000, size=20_000, replace=False)
        tbl = _probe_table(np.arange(2_000_000, 2_000_400))  # disjoint from build
        summary = summarize_build(build, ndv_limit=100)      # force bloom
        assert summary.bloom is not None
        res = prune_probe(ScanSet.full(tbl.num_partitions), tbl.stats, "k", summary)
        assert len(res.scan) == 0  # range check already removes everything
        # now overlapping but sparse probe values -> bloom must do the work
        tbl2 = _probe_table(np.arange(500_000, 500_400))
        res2 = prune_probe(ScanSet.full(tbl2.num_partitions), tbl2.stats, "k", summary)
        # partitions whose 4-value ranges miss every build key get pruned
        assert res2.pruned_by_bloom > 0 or len(res2.scan) < tbl2.num_partitions

    def test_empty_build_removes_probe_scan(self):
        tbl = _probe_table(np.arange(40))
        summary = summarize_build(np.zeros(0, dtype=np.int64))
        res = prune_probe(ScanSet.full(tbl.num_partitions), tbl.stats, "k", summary)
        assert len(res.scan) == 0  # the paper's 100%-pruned case

    def test_fractional_probe_range_not_falsely_pruned(self):
        """Regression (ISSUE 3): on a float key column the narrow-range
        enumeration probed only integer offsets from pmin — for the range
        [0.6, 1.4] it tested the single candidate trunc(0.6) = 0 and
        falsely pruned the partition containing the joinable key 1.2.
        Float columns must skip enumeration entirely (skip = keep)."""
        tbl = Table.build("probe", {"k": np.array([0.6, 1.4])},
                          rows_per_partition=2)
        assert tbl.stats.column("k").kind == "float"
        build = np.array([1.2])
        summary = summarize_build(build, ndv_limit=0)       # force Bloom
        assert summary.bloom is not None
        # guard: the regression is only visible if 0 isn't a false positive
        assert not summary.bloom.contains(np.array([0])).any()
        res = prune_probe(ScanSet.full(tbl.num_partitions), tbl.stats, "k",
                          summary)
        assert 0 in res.scan.part_ids.tolist()
        assert res.pruned_by_bloom == 0

    @settings(max_examples=60, deadline=None)
    @given(
        build=st.lists(st.floats(-50, 50).map(lambda x: round(x * 4) / 4),
                       min_size=1, max_size=40),
        probe=st.lists(st.floats(-50, 50).map(lambda x: round(x * 4) / 4),
                       min_size=4, max_size=80),
    )
    def test_never_prunes_joinable_fractional_keys(self, build, probe):
        """Hypothesis regression for the float-domain enumeration bug:
        quarter-step keys (exact in binary, frequently joinable) through
        a forced Bloom summary must never lose a joinable partition."""
        build = np.asarray(build, dtype=np.float64)
        tbl = Table.build("probe", {"k": np.asarray(probe, np.float64)},
                          rows_per_partition=4)
        summary = summarize_build(build, ndv_limit=0)       # force Bloom
        res = prune_probe(ScanSet.full(tbl.num_partitions), tbl.stats, "k",
                          summary)
        kept = set(res.scan.part_ids.tolist())
        for p in range(tbl.num_partitions):
            v, _ = tbl.partition_ctx(p).col("k")
            if np.isin(v, build).any():
                assert p in kept, f"pruned joinable partition {p}"

    def test_extreme_int64_range_width_does_not_overflow(self):
        """Regression (ISSUE 3): width = (pmax - pmin + 1).astype(int64)
        overflowed for int64-extreme ranges (numpy warns/raises on the
        invalid cast).  Width is now compared in float64 first — such
        partitions simply aren't narrow and must be kept."""
        vals = np.array([-2**62, 2**62], dtype=np.int64)
        tbl = Table.build("probe", {"k": vals}, rows_per_partition=2)
        summary = summarize_build(np.arange(5000, dtype=np.int64),
                                  ndv_limit=100)            # force Bloom
        assert summary.bloom is not None
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            res = prune_probe(ScanSet.full(tbl.num_partitions), tbl.stats,
                              "k", summary)
        assert 0 in res.scan.part_ids.tolist()              # range overlaps

    @settings(max_examples=80, deadline=None)
    @given(
        build=st.lists(st.integers(0, 500), min_size=0, max_size=80),
        probe=st.lists(st.integers(0, 500), min_size=4, max_size=200),
        ndv_limit=st.sampled_from([2, 4096]),
    )
    def test_never_prunes_joinable_partition(self, build, probe, ndv_limit):
        """The Sec. 6.2 guarantee: may miss prunable partitions, but never
        prunes one containing a joinable key."""
        build = np.asarray(build, dtype=np.int64)
        tbl = _probe_table(probe)
        summary = summarize_build(build, ndv_limit=ndv_limit)
        res = prune_probe(ScanSet.full(tbl.num_partitions), tbl.stats, "k", summary)
        kept = set(res.scan.part_ids.tolist())
        for p in range(tbl.num_partitions):
            ctx = tbl.partition_ctx(p)
            v, _ = ctx.col("k")
            if np.isin(v, build).any():
                assert p in kept, f"pruned joinable partition {p}"


def _probe_keys(layout, rng):
    """wide: TPC-H-like keys uniform over 2^33, so every partition's key
    range is far wider than the enumeration limit.  narrow: clustered
    keys, sorted, each partition ~800 values wide."""
    if layout == "wide":
        return rng.integers(0, 2**33, 4096)
    return np.sort(rng.integers(0, 100_000, 8192))


class TestLazyBloom:
    @pytest.mark.parametrize("layout", ["wide", "narrow"])
    def test_lazy_filter_prunes_as_the_eager_one(self, layout):
        """The filter is built on first read: a summary whose filter was
        built at once prunes the very same partitions, and ships the same
        size.  A probe side with no enumerable partition never builds it;
        an enumerable one builds the words BlockedBloom builds."""
        rng = np.random.default_rng(31)
        probe = _probe_keys(layout, rng)
        tbl = _probe_table(probe, rows_per_partition=64)
        if layout == "wide":
            build = np.concatenate([probe[::3], rng.integers(0, 2**33, 3000)])
        else:   # keys in every other 5,000-wide block: the gaps prune
            build = rng.integers(0, 100_000, 20_000)
            build = build[(build // 5000) % 2 == 0]
        uniq = np.unique(build)
        assert uniq.size > 4096
        lazy = summarize_build(build)
        eager = summarize_build(build)
        assert eager.bloom is not None and eager.bloom_built
        assert lazy.kind == eager.kind == "bloom" and not lazy.bloom_built
        assert lazy.n_blocks == BlockedBloom(uniq.size).n_blocks
        assert lazy.size_bytes == eager.bloom.size_bytes + 16

        scan = ScanSet.full(tbl.num_partitions)
        got = prune_probe(scan, tbl.stats, "k", lazy)
        want = prune_probe(scan, tbl.stats, "k", eager)
        np.testing.assert_array_equal(got.scan.part_ids, want.scan.part_ids)
        assert ((got.pruned_by_range, got.pruned_by_distinct,
                 got.pruned_by_bloom)
                == (want.pruned_by_range, want.pruned_by_distinct,
                    want.pruned_by_bloom))
        assert lazy.size_bytes == eager.size_bytes
        if layout == "wide":
            assert not lazy.bloom_built
        else:
            assert lazy.bloom_built and got.pruned_by_bloom > 0
            ref = BlockedBloom(uniq.size)
            ref.add(uniq)
            np.testing.assert_array_equal(lazy.bloom.words, ref.words)
