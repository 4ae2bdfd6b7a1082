"""Device metadata plane: batched multi-query kernel vs per-query kernel
vs the f64 host oracle; DeviceStatsCache staging/invalidation; the f32
precision contract; the vectorized block-topk staging."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import expr as E
from repro.core.device_stats import (DeviceStats, DeviceStatsCache,
                                     cast_bounds_f32, cast_stats_f32,
                                     round_down_f32, round_up_f32)
from repro.core.flow import PruningPipeline, Query, TableScanSpec
from repro.core.metadata import (FULL_MATCH, NO_MATCH, ColumnMeta,
                                 PartitionStats)
from repro.core.prune_filter import eval_ranges_tv, extract_ranges
from repro.data.table import Table
from repro.kernels import minmax_prune_batched, ops, ref
from repro.serve.prune_service import PruningService

from helpers import small_tables


def make_stats(P, C, rng, with_nulls=True, with_empty=True):
    """Randomized f32-exact stats incl. all-null (empty-interval) partitions."""
    mins = rng.integers(-1000, 1000, size=(P, C)).astype(np.float64)
    maxs = mins + rng.integers(0, 100, size=(P, C)).astype(np.float64)
    nulls = np.zeros((P, C), dtype=np.int64)
    if with_nulls:
        nulls = (rng.random((P, C)) < 0.25).astype(np.int64) * 3
    if with_empty:
        empty = rng.random((P, C)) < 0.15
        mins = np.where(empty, np.inf, mins)
        maxs = np.where(empty, -np.inf, maxs)
    return PartitionStats(
        columns=[ColumnMeta(f"c{i}", "int") for i in range(C)],
        mins=mins, maxs=maxs, null_counts=nulls,
        row_counts=np.full(P, 10, dtype=np.int64),
    )


def make_range_lists(Q, C, rng, max_k=5):
    out = []
    for _ in range(Q):
        k = int(rng.integers(0, max_k + 1))
        ranges = []
        for _ in range(k):
            lo = float(rng.integers(-1100, 1100))
            ranges.append((int(rng.integers(0, C)), lo,
                           lo + float(rng.integers(0, 300))))
        out.append(ranges)
    return out


@st.composite
def batched_problems(draw):
    P = draw(st.integers(1, 400))
    C = draw(st.integers(1, 6))
    Q = draw(st.integers(1, 20))
    seed = draw(st.integers(0, 2**31))
    return P, C, Q, seed


class TestBatchedKernelParity:
    """tv[q] from one batched launch == per-query kernel == f64 oracle."""

    @settings(max_examples=25, deadline=None)
    @given(problem=batched_problems())
    def test_batched_matches_oracle_and_per_query(self, problem):
        P, C, Q, seed = problem
        rng = np.random.default_rng(seed)
        stats = make_stats(P, C, rng)
        dstats = DeviceStats.stage(stats)
        range_lists = make_range_lists(Q, C, rng)
        for mode in ("ref", "interpret"):
            tv = ops.prune_ranges_batched_device(range_lists, dstats, mode=mode)
            assert tv.shape == (Q, P)
            for qi, ranges in enumerate(range_lists):
                oracle = eval_ranges_tv(ranges, stats)
                np.testing.assert_array_equal(tv[qi], oracle, err_msg=f"q={qi}")
                if ranges:
                    single = ops.prune_ranges_device(ranges, stats, mode="ref")
                    np.testing.assert_array_equal(tv[qi], single)

    def test_block_boundary_shapes(self):
        """Q and P crossing the BLOCK_Q/BLOCK_P tile edges."""
        rng = np.random.default_rng(7)
        for P in (1, 2048, 2049):
            stats = make_stats(P, 3, rng)
            dstats = DeviceStats.stage(stats)
            for Q in (1, 7, 8, 9, 33):
                range_lists = make_range_lists(Q, 3, rng, max_k=3)
                tv = ops.prune_ranges_batched_device(
                    range_lists, dstats, mode="interpret")
                for qi, ranges in enumerate(range_lists):
                    np.testing.assert_array_equal(
                        tv[qi], eval_ranges_tv(ranges, stats))

    def test_kernel_raw_matches_ref_raw(self):
        """The pallas kernel against the jnp oracle on identical inputs."""
        rng = np.random.default_rng(3)
        C, P, Q, Kb = 4, 300, 16, 4
        mins = rng.uniform(-100, 100, (C, P)).astype(np.float32)
        maxs = mins + rng.uniform(0, 50, (C, P)).astype(np.float32)
        demote = (rng.random((C, P)) < 0.2).astype(np.float32)
        cids = rng.integers(0, C, (Q, Kb)).astype(np.int32)
        lo = rng.uniform(-120, 120, (Q, Kb)).astype(np.float32)
        hi = lo + rng.uniform(0, 100, (Q, Kb)).astype(np.float32)
        # sprinkle no-op padding slots
        noop = rng.random((Q, Kb)) < 0.3
        lo = np.where(noop, -np.inf, lo).astype(np.float32)
        hi = np.where(noop, np.inf, hi).astype(np.float32)
        args = [jnp.asarray(a) for a in (cids, lo, hi, mins, maxs, demote)]
        out_k = minmax_prune_batched(*args, interpret=True)
        out_r = ref.minmax_prune_batched_ref(*args)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))

    def test_ref_slab_chunking_is_seamless(self, monkeypatch):
        """The memory-bounded P-slab path equals the one-shot path."""
        rng = np.random.default_rng(11)
        stats = make_stats(5000, 3, rng)
        dstats = DeviceStats.stage(stats)
        range_lists = make_range_lists(9, 3, rng)
        whole = ops.prune_ranges_batched_device(range_lists, dstats, mode="ref")
        monkeypatch.setattr(ops, "_REF_SLAB_ELEMS", 4096)
        slabbed = ops.prune_ranges_batched_device(range_lists, dstats, mode="ref")
        np.testing.assert_array_equal(whole, slabbed)

    @settings(max_examples=15, deadline=None)
    @given(tbl=small_tables())
    def test_real_tables_end_to_end(self, tbl):
        preds = [
            (E.col("x") >= -10) & (E.col("y") <= 700),
            E.col("y") == 400,
            E.startswith(E.col("s"), "Alpine"),
        ]
        range_lists = [extract_ranges(p, tbl.stats) for p in preds]
        assert all(r is not None for r in range_lists)
        dstats = DeviceStats.stage(tbl.stats)
        tv = ops.prune_ranges_batched_device(range_lists, dstats, mode="ref")
        for qi, ranges in enumerate(range_lists):
            np.testing.assert_array_equal(tv[qi], eval_ranges_tv(ranges, tbl.stats))


class TestPrecisionContract:
    """core/device_stats.py: f32 downcast is widening + demoting."""

    def test_directed_rounding(self):
        vals = np.array([2**24 + 1, -(2**24) - 1, 0.1, -0.1, np.inf, -np.inf])
        lo = round_down_f32(vals)
        hi = round_up_f32(vals)
        assert (lo.astype(np.float64) <= vals).all()
        assert (hi.astype(np.float64) >= vals).all()

    @pytest.mark.parametrize("base, exact", [
        (2**24, True), (2**53 - 4096, True), (2**54, False)])
    def test_big_int_keys_never_false_no_match_or_full(self, base, exact):
        """int64 keys > 2**24: up to 2**53 the batched path evaluates
        them on exact limbs, bit-identical to the f64 oracle; beyond it
        FULL may degrade to PARTIAL, but NO_MATCH and FULL are never
        falsely claimed (the regression the cast contract guards).  The
        per-query path keeps the widening contract throughout."""
        P, C = 64, 2
        rng = np.random.default_rng(5)
        mins = (base + rng.integers(0, 1000, size=(P, C))).astype(np.float64)
        maxs = mins + rng.integers(0, 9, size=(P, C)).astype(np.float64)
        stats = PartitionStats(
            columns=[ColumnMeta(f"c{i}", "int") for i in range(C)],
            mins=mins, maxs=maxs,
            null_counts=np.zeros((P, C), dtype=np.int64),
            row_counts=np.full(P, 10, dtype=np.int64),
        )
        dstats = DeviceStats.stage(stats)
        assert bool(dstats.wide) == exact
        range_lists = []
        for _ in range(32):
            lo = float(base + rng.integers(0, 1000))
            range_lists.append([(int(rng.integers(0, C)), lo,
                                 lo + float(rng.integers(0, 12)))])
        tv = ops.prune_ranges_batched_device(range_lists, dstats, mode="ref")
        some_demotion = False
        for qi, ranges in enumerate(range_lists):
            oracle = eval_ranges_tv(ranges, stats)
            single = ops.prune_ranges_device(ranges, stats, mode="ref")
            for got in (tv[qi], single):
                # never a false NO_MATCH: every pruned partition truly empty
                assert ((got == NO_MATCH) <= (oracle == NO_MATCH)).all()
                # never a false FULL: FULL only where the oracle proves it
                assert ((got == FULL_MATCH) <= (oracle == FULL_MATCH)).all()
            if exact:
                np.testing.assert_array_equal(tv[qi], oracle)
            some_demotion |= bool((tv[qi] != oracle).any())
        # beyond 2**53 the contract is exercised: at least one FULL
        # degraded to PARTIAL
        assert some_demotion != exact

    def test_infinite_float_stats_safe_on_kernel_path(self):
        """Float columns holding real ±inf values: the finite clamp must
        demote, never false-NO/false-FULL — on the kernel path too (the
        one-hot gather would NaN on raw ±inf; regression from review)."""
        fmax = float(np.finfo(np.float32).max)
        mins = np.array([[-np.inf], [0.0], [5.0], [np.inf]], dtype=np.float64)
        maxs = np.array([[5.0], [np.inf], [9.0], [-np.inf]], dtype=np.float64)
        stats = PartitionStats(
            columns=[ColumnMeta("f", "float")],
            mins=mins.T.copy().T.reshape(4, 1), maxs=maxs.reshape(4, 1),
            null_counts=np.zeros((4, 1), dtype=np.int64),
            row_counts=np.full(4, 3, dtype=np.int64),
        )
        dstats = DeviceStats.stage(stats)
        range_lists = [
            [(0, -fmax, 10.0)],            # reviewer repro: was false FULL
            [(0, np.inf, np.inf)],         # x == inf: was false NO
            [(0, -np.inf, 4.0)],           # one-sided, crosses partition 0
            [(0, 6.0, np.inf)],
        ]
        for mode in ("ref", "interpret"):
            tv = ops.prune_ranges_batched_device(range_lists, dstats, mode=mode)
            for qi, ranges in enumerate(range_lists):
                oracle = eval_ranges_tv(ranges, stats)
                got = tv[qi]
                assert ((got == NO_MATCH) <= (oracle == NO_MATCH)).all(), \
                    (mode, qi, got, oracle)
                assert ((got == FULL_MATCH) <= (oracle == FULL_MATCH)).all(), \
                    (mode, qi, got, oracle)

    def test_stats_cast_flags_inexact(self):
        mins = np.array([[0.0, 2**24 + 1]])
        maxs = np.array([[1.0, 2**24 + 3]])
        m32, x32, inexact = cast_stats_f32(mins, maxs)
        assert not inexact[0, 0] and inexact[0, 1]
        assert m32[0, 1].astype(np.float64) <= 2**24 + 1
        assert x32[0, 1].astype(np.float64) >= 2**24 + 3

    def test_bounds_cast_flags_inexact(self):
        lo, hi, exact = cast_bounds_f32([0.0, 2**24 + 1], [10.0, 2**25 + 1])
        assert exact[0] and not exact[1]


class TestDeviceStatsCache:
    def _table(self, n=600, seed=0):
        rng = np.random.default_rng(seed)
        return Table.build(
            "t", {"v": rng.integers(0, 1000, n).astype(np.int64)},
            rows_per_partition=50)

    def test_staged_once_then_hits(self):
        cache = DeviceStatsCache()
        tbl = self._table()
        a = cache.get(tbl)
        b = cache.get(tbl)
        assert a is b
        assert (cache.hits, cache.misses) == (1, 1)
        assert cache.resident_bytes == a.nbytes > 0

    def test_version_bump_invalidates(self):
        """insert_partitions bumps the version -> stale plane is dropped
        and the table re-stages (the DML-safety requirement)."""
        from repro.core.predicate_cache import TableVersion
        cache = DeviceStatsCache()
        tbl = self._table()
        tv = TableVersion(tbl.num_partitions)
        first = cache.get(tbl, tv)
        tv.insert_partitions(0)          # version bump, same partition count
        second = cache.get(tbl, tv)
        assert second is not first
        assert cache.misses == 2
        # the superseded staging was dropped, not retained alongside
        assert len(cache.entries) == 1

    def test_insert_partitions_restages_grown_table(self):
        from repro.core.predicate_cache import TableVersion
        cache = DeviceStatsCache()
        tbl = self._table(n=600)
        tv = TableVersion(tbl.num_partitions)
        cache.get(tbl, tv)
        grown = self._table(n=700)       # same name, more partitions
        tv.insert_partitions(grown.num_partitions - tbl.num_partitions)
        ds = cache.get(grown, tv)
        assert ds.num_partitions == grown.num_partitions
        assert cache.misses == 2         # fresh staging, never the stale plane

    def test_live_same_name_tables_do_not_thrash(self):
        """Two distinct live tables sharing a name must coexist in the
        cache — alternating between them stages each exactly once."""
        cache = DeviceStatsCache()
        a = self._table(seed=1)
        b = self._table(seed=2)          # same name "t", different stats
        for _ in range(3):
            cache.get(a)
            cache.get(b)
        assert cache.misses == 2
        assert cache.hits == 4
        assert len(cache.entries) == 2

    def test_rebuilt_table_never_hits_stale_plane(self):
        """A rebuilt table (same name, same partition count, new data)
        must re-stage — a stale hit would false-NO_MATCH, losing rows
        (regression from review)."""
        rng = np.random.default_rng(0)
        t1 = Table.build("t", {"v": np.arange(100, dtype=np.int64)},
                         rows_per_partition=10)
        pipe = PruningPipeline(filter_mode="device")
        pipe.run(Query(scans={"t": TableScanSpec(t1, E.col("v") >= 0)}))
        t2 = Table.build("t", {"v": np.arange(100, 200, dtype=np.int64)},
                         rows_per_partition=10)
        q = Query(scans={"t": TableScanSpec(t2, E.col("v") >= 190)})
        dev = pipe.run(q)
        host = PruningPipeline(filter_mode="host").run(q)
        np.testing.assert_array_equal(dev.scan_sets["t"].part_ids,
                                      host.scan_sets["t"].part_ids)
        assert len(dev.scan_sets["t"]) == 1

    def test_explicit_invalidation_and_lru(self):
        cache = DeviceStatsCache(max_entries=2)
        tables = [Table.build(f"t{i}", {"v": np.arange(60, dtype=np.int64)},
                              rows_per_partition=10) for i in range(3)]
        for t in tables:
            cache.get(t)
        assert len(cache.entries) == 2          # LRU evicted t0
        cache.invalidate("t2")
        assert len(cache.entries) == 1
        cache.on_update("t1", "v")
        assert len(cache.entries) == 0


class TestPruningService:
    def _tables(self, seed=0):
        rng = np.random.default_rng(seed)
        n = 2000
        t = Table.build("t", {
            "v": rng.permutation(np.arange(n)).astype(np.int64),
            "w": np.sort(rng.integers(0, 10_000, n)).astype(np.int64),
        }, rows_per_partition=50,
            nulls={"v": rng.random(n) < 0.05})
        u = Table.build("u", {
            "a": rng.integers(-50, 50, 400).astype(np.int64)},
            rows_per_partition=20)
        return t, u

    def _queries(self, t, u):
        return [
            Query(scans={"t": TableScanSpec(
                t, (E.col("w") >= 5000) & (E.col("w") < 6000))}),
            Query(scans={"t": TableScanSpec(t, E.col("v") > 1500)}),
            Query(scans={"t": TableScanSpec(
                t, (E.col("w") >= 5000) | (E.col("v") < 10))}),   # fallback
            Query(scans={"u": TableScanSpec(u, E.col("a") == 0)}),
            Query(scans={"t": TableScanSpec(t)}),                 # TruePred
        ]

    def test_batch_equals_host_pipeline(self):
        t, u = self._tables()
        queries = self._queries(t, u)
        svc = PruningService(mode="ref")
        reports = svc.run_batch(queries)
        host = PruningPipeline(filter_mode="host")
        for q, rep in zip(queries, reports):
            h = host.run(q)
            for name in q.scans:
                np.testing.assert_array_equal(
                    rep.scan_sets[name].part_ids, h.scan_sets[name].part_ids)
                np.testing.assert_array_equal(
                    rep.scan_sets[name].match, h.scan_sets[name].match)

    def test_one_launch_per_table_group(self):
        t, u = self._tables()
        svc = PruningService(mode="ref")
        svc.prune_batch(self._queries(t, u))
        assert svc.counters.launches == 2        # tables t and u
        assert svc.counters.host_fallbacks == 1  # the OR predicate
        assert svc.cache.misses == 2             # staged once per table

    def test_second_batch_reuses_resident_plane(self):
        t, u = self._tables()
        svc = PruningService(mode="ref")
        svc.prune_batch(self._queries(t, u))
        misses = svc.cache.misses
        svc.prune_batch(self._queries(t, u))
        assert svc.cache.misses == misses        # pure cache hits

    def test_dml_notifications_invalidate(self):
        t, u = self._tables()
        svc = PruningService(mode="ref")
        svc.register(t)
        svc.prune_batch(self._queries(t, u))
        misses = svc.cache.misses
        svc.notify_insert("t", 2)
        svc.prune_batch(self._queries(t, u))
        assert svc.cache.misses == misses + 1    # t re-staged, u still hit

    def test_pipeline_device_mode_delegates(self):
        t, u = self._tables()
        pipe = PruningPipeline(filter_mode="device")
        for q in self._queries(t, u):
            pipe.run(q)
        svc = pipe.device_service()
        assert svc.counters.launches >= 3
        assert svc.cache.hits > 0                # resident plane reused


class TestBlockTopKVectorized:
    @staticmethod
    def _loop_reference(values, part_bounds, k, mask=None):
        """The original per-partition Python loop, kept as the oracle."""
        P = len(part_bounds) - 1
        out = np.full((P, k), -np.inf, dtype=np.float32)
        for p in range(P):
            s, e = int(part_bounds[p]), int(part_bounds[p + 1])
            v = values[s:e]
            if mask is not None:
                v = v[mask[s:e]]
            if v.size:
                top = np.sort(v)[::-1][:k]
                out[p, : len(top)] = top
        return out

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 400), k=st.sampled_from([1, 2, 4, 8]),
           seed=st.integers(0, 2**31), masked=st.booleans())
    def test_matches_loop_reference(self, n, k, seed, masked):
        rng = np.random.default_rng(seed)
        vals = rng.uniform(-1000, 1000, n).astype(np.float32)
        cuts = np.unique(rng.integers(0, n + 1, size=rng.integers(0, 12)))
        bounds = np.unique(np.concatenate([[0], cuts, [n]]))
        mask = (rng.random(n) < 0.6) if masked else None
        got = ops.build_block_topk(vals, bounds, k, mask=mask)
        want = self._loop_reference(vals, bounds, k, mask=mask)
        np.testing.assert_array_equal(got, want)

    def test_empty_and_degenerate(self):
        out = ops.build_block_topk(np.zeros(0, np.float32), np.array([0]), 4)
        assert out.shape == (0, 4)
        out = ops.build_block_topk(
            np.array([5.0], np.float32), np.array([0, 1]), 4,
            mask=np.array([False]))
        assert (out == -np.inf).all()

    def test_offset_bounds(self):
        """part_bounds need not start at row 0 (kernels_bench slices)."""
        vals = np.arange(100, dtype=np.float32)
        bounds = np.array([40, 60, 100])
        got = ops.build_block_topk(vals, bounds, 2)
        want = self._loop_reference(vals, bounds, 2)
        np.testing.assert_array_equal(got, want)


class TestBenchSmoke:
    def test_batched_prune_bench_runs(self, tmp_path):
        from benchmarks.bench_batched_prune import run
        json_path = str(tmp_path / "BENCH_batched_prune.json")
        rows, cells = run(grid_p=(512,), grid_q=(1, 4), json_path=json_path)
        assert len(cells) == 2
        import json as _json
        with open(json_path) as f:
            payload = _json.load(f)
        assert payload["bench"] == "batched_prune"
        assert len(payload["grid"]) == 2

    def test_run_py_csv_parse_and_json(self, tmp_path):
        from benchmarks.run import parse_csv_rows, write_module_json
        rows = parse_csv_rows(
            "name,us_per_call,derived\nfoo,1.5,bar\n# comment\nbad line\n")
        assert rows == [dict(name="foo", us_per_call=1.5, derived="bar")]
        path = write_module_json(str(tmp_path), "m", rows, 0.1)
        import json as _json
        with open(path) as f:
            assert _json.load(f)["rows"] == rows
