"""Unified runtime pruning engine (ISSUES 2+3): batched join-overlap,
Bloom-probe and top-k boundary-init kernels vs their oracles; technique-
executor parity — ``PruningService.run_batch`` vs per-query
``PruningPipeline.run`` vs the host engine (distinct and Bloom summaries);
per-technique launch bounding and counters; DML invalidation of the
join-key / enumeration / block-top-k planes; overall_ratio guard."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro.core import expr as E
from repro.core.device_stats import DeviceStatsCache
from repro.core.flow import (JoinSpec, PruningPipeline, PruningReport, Query,
                             TableScanSpec, TechniqueReport)
from repro.core.metadata import FULL_MATCH, ScanSet
from repro.core.prune_join import (BlockedBloom, prune_probe, summarize_build)
from repro.core.prune_topk import TopKResult
from repro.data.table import Table
from repro.kernels import (bloom_probe_batched, join_overlap_batched, ops,
                           ref, topk_init_batched)
from repro.serve.prune_service import PruningService


# ---------------------------------------------------------------------------
# join_overlap_batched kernel
# ---------------------------------------------------------------------------

@st.composite
def batched_overlap_problems(draw):
    P = draw(st.integers(1, 400))
    Q = draw(st.integers(1, 12))
    seed = draw(st.integers(0, 2**31))
    return P, Q, seed


def _make_overlap_inputs(P, Q, rng):
    pmin = rng.integers(0, 10_000, size=P).astype(np.float32)
    pmax = pmin + rng.integers(0, 100, size=P).astype(np.float32)
    fmax = np.float32(np.finfo(np.float32).max)
    empty = rng.random(P) < 0.1
    pmin = np.where(empty, fmax, pmin).astype(np.float32)
    pmax = np.where(empty, -fmax, pmax).astype(np.float32)
    lists = [np.unique(rng.integers(0, 10_000,
                                    size=rng.integers(1, 200))).astype(np.float32)
             for _ in range(Q)]
    return pmin, pmax, lists


class TestJoinOverlapBatchedKernel:
    @settings(max_examples=20, deadline=None)
    @given(problem=batched_overlap_problems())
    def test_kernel_matches_ref_and_brute(self, problem):
        P, Q, seed = problem
        rng = np.random.default_rng(seed)
        pmin, pmax, lists = _make_overlap_inputs(P, Q, rng)
        dist = ops.pack_distinct(lists)
        out_k = np.asarray(join_overlap_batched(
            jnp.asarray(dist), jnp.asarray(pmin), jnp.asarray(pmax),
            interpret=True))[:Q]
        out_r = np.asarray(ref.join_overlap_batched_ref(
            jnp.asarray(dist), jnp.asarray(pmin), jnp.asarray(pmax)))[:Q]
        np.testing.assert_array_equal(out_k, out_r)
        for qi, d in enumerate(lists):
            brute = np.array([((d >= lo) & (d <= hi)).any()
                              for lo, hi in zip(pmin, pmax)], dtype=np.int32)
            np.testing.assert_array_equal(out_k[qi], brute, err_msg=f"q={qi}")

    def test_wrapper_modes_agree_and_single_query_row(self):
        rng = np.random.default_rng(3)
        pmin, pmax, lists = _make_overlap_inputs(3000, 9, rng)
        pmin_d, pmax_d = jnp.asarray(pmin), jnp.asarray(pmax)
        ref_hit = ops.join_overlap_batched_device(lists, pmin_d, pmax_d,
                                                  mode="ref")
        int_hit = ops.join_overlap_batched_device(lists, pmin_d, pmax_d,
                                                  mode="interpret")
        np.testing.assert_array_equal(ref_hit, int_hit)
        # a Q=1 batch row equals the same query inside a bigger batch
        solo = ops.join_overlap_batched_device([lists[4]], pmin_d, pmax_d,
                                               mode="ref")
        np.testing.assert_array_equal(solo[0], ref_hit[4])

    def test_large_p_modes_agree(self):
        """P well past the kernel tile edge: numpy ref == interpret."""
        rng = np.random.default_rng(11)
        pmin, pmax, lists = _make_overlap_inputs(5000, 9, rng)
        pmin_d, pmax_d = jnp.asarray(pmin), jnp.asarray(pmax)
        ref_hit = ops.join_overlap_batched_device(lists, pmin_d, pmax_d, "ref")
        int_hit = ops.join_overlap_batched_device(lists, pmin_d, pmax_d,
                                                  "interpret")
        np.testing.assert_array_equal(ref_hit, int_hit)


# ---------------------------------------------------------------------------
# topk_init_batched kernel
# ---------------------------------------------------------------------------

@st.composite
def init_problems(draw):
    P = draw(st.integers(1, 300))
    K = draw(st.sampled_from([2, 4, 8]))
    k = draw(st.sampled_from([1, 4, 8, 16]))
    Q = draw(st.integers(1, 10))
    seed = draw(st.integers(0, 2**31))
    return P, K, k, Q, seed


def _make_init_inputs(P, K, Q, rng):
    plane = rng.integers(-1000, 1000, size=(P, K)).astype(np.float32)
    fill = rng.integers(0, K + 1, size=P)
    for p in range(P):
        plane[p, fill[p]:] = -np.inf
    plane = -np.sort(-plane, axis=1)
    mask = (rng.random((Q, P)) < 0.3).astype(np.float32)
    return plane, mask


def _init_oracle(plane, mask, k):
    Q = mask.shape[0]
    out = np.full((Q, k), -np.inf, dtype=np.float32)
    for qi in range(Q):
        vals = plane[mask[qi] > 0].ravel()
        vals = np.sort(vals[vals > -np.inf])[::-1][:k]
        out[qi, : len(vals)] = vals
    return out


class TestTopKInitBatchedKernel:
    @settings(max_examples=20, deadline=None)
    @given(problem=init_problems())
    def test_kernel_matches_ref_and_oracle(self, problem):
        P, K, k, Q, seed = problem
        rng = np.random.default_rng(seed)
        plane, mask = _make_init_inputs(P, K, Q, rng)
        out_k = np.asarray(topk_init_batched(
            jnp.asarray(plane), jnp.asarray(mask), k, interpret=True))
        out_r = np.asarray(ref.topk_init_batched_ref(
            jnp.asarray(plane), jnp.asarray(mask), k))
        oracle = _init_oracle(plane, mask, k)
        np.testing.assert_array_equal(out_k, oracle)
        np.testing.assert_array_equal(out_r, oracle)

    def test_wrapper_modes_agree_across_blocks(self):
        """P crossing BLOCK_PI and Q crossing BLOCK_QI tile edges."""
        rng = np.random.default_rng(5)
        for P, Q in ((1, 1), (129, 9), (300, 17)):
            plane, mask = _make_init_inputs(P, 8, Q, rng)
            plane_d = jnp.asarray(plane)
            out_ref = ops.topk_init_batched_device(plane_d, mask, 4, "ref")
            out_int = ops.topk_init_batched_device(plane_d, mask, 4,
                                                   "interpret")
            np.testing.assert_array_equal(out_ref, out_int)
            np.testing.assert_array_equal(out_ref, _init_oracle(plane, mask, 4))


# ---------------------------------------------------------------------------
# bloom_probe_batched kernel (ISSUE 3 tentpole)
# ---------------------------------------------------------------------------

@st.composite
def bloom_probe_problems(draw):
    P = draw(st.integers(1, 300))
    Q = draw(st.integers(1, 6))
    enum_limit = draw(st.sampled_from([4, 32, 96]))
    seed = draw(st.integers(0, 2**31))
    return P, Q, enum_limit, seed


def _make_bloom_inputs(P, Q, rng):
    """Random enumeration plane (negative domains, non-narrow rows) and
    Q filters of mixed NDV (mixed n_blocks exercises the tiling)."""
    pmin = rng.integers(-5000, 5000, size=P).astype(np.int32)
    width = rng.integers(0, 120, size=P).astype(np.int32)
    width[rng.random(P) < 0.25] = 0
    blooms = []
    for _ in range(Q):
        keys = np.unique(rng.integers(-6000, 6000,
                                      size=int(rng.integers(10, 4000))))
        b = BlockedBloom(len(keys))
        b.add(keys)
        blooms.append(b)
    return pmin, width, blooms


def _bloom_brute(blooms, pmin, width, enum_limit):
    """The (fixed) host matcher's enumeration, partition by partition."""
    Q, P = len(blooms), len(pmin)
    hit = np.ones((Q, P), dtype=np.int32)
    for qi, b in enumerate(blooms):
        for p in range(P):
            if 0 < width[p] <= enum_limit:
                cand = np.int64(pmin[p]) + np.arange(width[p])
                hit[qi, p] = int(b.contains(cand).any())
    return hit


class TestBloomProbeBatchedKernel:
    @settings(max_examples=15, deadline=None)
    @given(problem=bloom_probe_problems())
    def test_kernel_matches_oracle_and_host_matcher(self, problem):
        """Device (interpret) == jnp oracle == host BlockedBloom probe,
        bit for bit — the ISSUE 3 acceptance parity."""
        P, Q, enum_limit, seed = problem
        rng = np.random.default_rng(seed)
        pmin, width, blooms = _make_bloom_inputs(P, Q, rng)
        brute = _bloom_brute(blooms, pmin, width, enum_limit)
        pmin_d = jnp.asarray(pmin)
        width_d = jnp.asarray(width)
        wmax = int(width.max()) if P else 0
        out_i = ops.bloom_probe_batched_device(
            blooms, pmin_d, width_d, wmax, enum_limit, mode="interpret")
        np.testing.assert_array_equal(out_i, brute)
        lo, hi = ops.pack_blooms(blooms)
        weff = jnp.where(width_d <= enum_limit, width_d, 0)
        eb = ops.enum_bucket(max(1, min(wmax, enum_limit)))
        out_r = np.asarray(ref.bloom_probe_batched_ref(
            jnp.asarray(lo), jnp.asarray(hi), pmin_d, weff, eb))[:Q]
        np.testing.assert_array_equal(out_r, brute)

    def test_sparse_fallback_matches_and_respects_part_ids(self):
        """The no-Pallas fallback equals the kernel on the entries it is
        allowed to read (each query's part_ids); other entries stay 1."""
        rng = np.random.default_rng(4)
        pmin, width, blooms = _make_bloom_inputs(500, 4, rng)
        pmin_d, width_d = jnp.asarray(pmin), jnp.asarray(width)
        wmax = int(width.max())
        full = ops.bloom_probe_batched_device(
            blooms, pmin_d, width_d, wmax, 64, mode="ref")
        np.testing.assert_array_equal(
            full, _bloom_brute(blooms, pmin, width, 64))
        ids = [np.sort(rng.choice(500, size=80, replace=False))
               for _ in blooms]
        part = ops.bloom_probe_batched_device(
            blooms, pmin_d, width_d, wmax, 64, mode="ref",
            part_ids_lists=ids)
        for qi, pid in enumerate(ids):
            np.testing.assert_array_equal(part[qi, pid], full[qi, pid])
            outside = np.setdiff1d(np.arange(500), pid)
            assert (part[qi, outside] == 1).all()

    def test_filter_tiling_preserves_probe_results(self):
        """pack_blooms tiles filters to the common pow-2 block bucket;
        probing under the larger mask must be identical — verified by
        batching a small filter next to a much larger one."""
        rng = np.random.default_rng(5)
        small_keys = np.arange(40, dtype=np.int64)        # few blocks
        big_keys = rng.integers(0, 10**6, size=30_000)    # many blocks
        small, big = BlockedBloom(40), BlockedBloom(30_000)
        small.add(small_keys)
        big.add(np.unique(big_keys))
        assert small.n_blocks < big.n_blocks
        pmin = np.arange(0, 200, dtype=np.int32)
        width = np.full(200, 3, dtype=np.int32)
        solo = ops.bloom_probe_batched_device(
            [small], jnp.asarray(pmin), jnp.asarray(width), 3, 64,
            mode="interpret")
        pair = ops.bloom_probe_batched_device(
            [small, big], jnp.asarray(pmin), jnp.asarray(width), 3, 64,
            mode="interpret")
        np.testing.assert_array_equal(pair[0], solo[0])
        np.testing.assert_array_equal(
            pair[1], _bloom_brute([big], pmin, width, 64)[0])


# ---------------------------------------------------------------------------
# technique-executor engine: batched == per-query == host
# ---------------------------------------------------------------------------

def _engine_tables(seed=0):
    rng = np.random.default_rng(seed)
    n = 3000
    events = Table.build("events", {
        "ts": np.sort(rng.integers(0, 1_000_000, n)).astype(np.int64),
        "uid": rng.integers(0, 400, n).astype(np.int64),
        "val": rng.integers(0, 10_000, n).astype(np.int64),
    }, rows_per_partition=30, nulls={"val": rng.random(n) < 0.03})
    users = Table.build("users", {
        "id": np.arange(400, dtype=np.int64),
        "grp": rng.integers(0, 8, 400).astype(np.int64),
    }, rows_per_partition=40)
    return events, users


def _mixed_workload(events, users, rng, n=64):
    """Filter + join + top-k + join-top-k queries (device-exact int keys)."""
    qs = []
    for i in range(n):
        lo = int(rng.integers(0, 900_000))
        pred = (E.col("ts") >= lo) & (E.col("ts") <= lo + 150_000)
        g = int(rng.integers(0, 8))
        kind = i % 4
        if kind == 0:
            qs.append(Query(scans={"e": TableScanSpec(events, pred)}))
        elif kind == 1:
            qs.append(Query(
                scans={"e": TableScanSpec(events, pred),
                       "u": TableScanSpec(users, E.col("grp") == g)},
                join=JoinSpec("u", "e", "id", "uid")))
        elif kind == 2:
            qs.append(Query(scans={"e": TableScanSpec(events, pred)},
                            limit=int(rng.integers(1, 30)),
                            order_by=("e", "val", bool(i % 8 < 4))))
        else:
            qs.append(Query(
                scans={"e": TableScanSpec(events, pred),
                       "u": TableScanSpec(users, E.col("grp") == g)},
                join=JoinSpec("u", "e", "id", "uid"),
                limit=10, order_by=("e", "val", True)))
    return qs


def _assert_reports_equal(a, b):
    assert a.scan_sets.keys() == b.scan_sets.keys()
    for name in a.scan_sets:
        np.testing.assert_array_equal(a.scan_sets[name].part_ids,
                                      b.scan_sets[name].part_ids)
        np.testing.assert_array_equal(a.scan_sets[name].match,
                                      b.scan_sets[name].match)
        assert a.per_scan[name].keys() == b.per_scan[name].keys()
        for tech in a.per_scan[name]:
            ra, rb = a.per_scan[name][tech], b.per_scan[name][tech]
            assert (ra.before, ra.after, ra.applied) == \
                (rb.before, rb.after, rb.applied), (name, tech)
            assert ra.detail == rb.detail, (name, tech)
    assert (a.topk is None) == (b.topk is None)
    if a.topk is not None:
        np.testing.assert_array_equal(a.topk.values, b.topk.values)
        np.testing.assert_array_equal(a.topk.scanned, b.topk.scanned)
        np.testing.assert_array_equal(a.topk.skipped, b.topk.skipped)
        assert a.topk_scan == b.topk_scan


class TestUnifiedEngine:
    def test_batched_equals_per_query_and_launches_bounded(self):
        """The ISSUE 2 acceptance shape: >= 64 mixed queries, batched
        run_batch output identical to per-query pipeline.run, with kernel
        launches per stage bounded by distinct table groups."""
        events, users = _engine_tables()
        rng = np.random.default_rng(1)
        queries = _mixed_workload(events, users, rng, n=64)
        svc = PruningService(mode="ref")
        pipe = PruningPipeline(filter_mode="device", service=svc)
        before = svc.counters.snapshot()
        batch = svc.run_batch(queries, pipe)
        after = svc.counters.snapshot()
        seq = [pipe.run(q) for q in queries]
        for b, s in zip(batch, seq):
            _assert_reports_equal(b, s)
        # launches per stage: bounded by table groups, not queries
        t = {k: after["technique"][k]["launches"]
             - before["technique"].get(k, dict(launches=0))["launches"]
             for k in after["technique"]}
        assert t["filter"] == 2          # tables e and u
        assert t["join"] == 1            # one (events, uid) group
        assert 1 <= t["topk"] <= 2       # (events, val) x {asc, desc}
        # only join-top-k queries (extra mask -> host-only init) fall back
        n_join_topk = sum(1 for q in queries
                          if q.is_topk and q.join is not None)
        fb = {k: after["technique"][k]["fallbacks"]
              - before["technique"].get(k, dict(fallbacks=0))["fallbacks"]
              for k in after["technique"]}
        assert fb["filter"] == 0 and fb["join"] == 0
        assert fb["topk"] == n_join_topk

    def test_device_engine_matches_host_on_exact_workload(self):
        """On int workloads (< 2**24, exact f32) the device join path
        prunes exactly like the host matcher; top-k values are identical
        and the device boundary-init only ever *adds* skips."""
        events, users = _engine_tables(seed=3)
        rng = np.random.default_rng(4)
        queries = _mixed_workload(events, users, rng, n=32)
        svc = PruningService(mode="ref")
        dev = PruningPipeline(filter_mode="device", service=svc)
        host = PruningPipeline(filter_mode="host")
        for q in queries:
            rd, rh = dev.run(q), host.run(q)
            for name in rh.scan_sets:
                np.testing.assert_array_equal(
                    rd.scan_sets[name].part_ids, rh.scan_sets[name].part_ids)
            if rh.topk is not None:
                np.testing.assert_array_equal(rd.topk.values, rh.topk.values)
                assert set(rh.topk.skipped) <= set(rd.topk.skipped)

    def test_report_counters_attribute_stages(self):
        events, users = _engine_tables(seed=5)
        rng = np.random.default_rng(6)
        queries = _mixed_workload(events, users, rng, n=16)
        svc = PruningService(mode="ref")
        reports = svc.run_batch(queries)
        snap = reports[0].counters
        assert snap["technique"]["filter"]["launches"] >= 1
        assert snap["technique"]["join"]["launches"] >= 1
        assert snap["technique"]["topk"]["launches"] >= 1
        # per-report technique details carry the execution path
        join_reps = [r.per_scan["e"]["join"] for r in reports
                     if "join" in r.per_scan.get("e", {})]
        assert join_reps and all(j.detail["path"] == "device"
                                 for j in join_reps)

    def test_disabled_filter_never_certifies_full_match(self):
        """enable_filter=False with a real predicate must not mark
        partitions FULL_MATCH — an uncertified FULL would seed the
        Sec. 5.4 boundary (host and device) from non-matching rows and
        return wrong (even empty) top-k results."""
        from repro.core.prune_topk import topk_oracle
        events, _users = _engine_tables(seed=21)
        pred = E.col("uid") <= 20           # selective, uncertified
        q = Query(scans={"e": TableScanSpec(events, pred)},
                  limit=5, order_by=("e", "val", True))
        oracle = topk_oracle(events, "val", 5, pred=pred)
        for pipe in (PruningPipeline(enable_filter=False),
                     PruningPipeline(enable_filter=False,
                                     filter_mode="device",
                                     service=PruningService(mode="ref"))):
            rep = pipe.run(q)
            assert (rep.scan_sets["e"].match != FULL_MATCH).all()
            np.testing.assert_array_equal(rep.topk.values, oracle)

    def test_bloom_summaries_take_device_path(self):
        """NDV above the distinct limit -> Bloom summary -> batched
        enumeration launch (ISSUE 3), same scan sets as the host pipeline
        and no host fallback on an integer key domain."""
        events, users = _engine_tables(seed=7)
        rng = np.random.default_rng(8)
        q = _mixed_workload(events, users, rng, n=2)[1]   # join query
        svc = PruningService(mode="ref")
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=2)
        rep = svc.run_batch([q], pipe)[0]
        assert rep.per_scan["e"]["join"].detail["path"] == "device"
        assert rep.per_scan["e"]["join"].detail["summary_kind"] == "bloom"
        assert svc.counters.technique["join_bloom"]["launches"] == 1
        assert svc.counters.technique["join_bloom"]["fallbacks"] == 0
        assert "join" not in svc.counters.technique  # no distinct work
        host = PruningPipeline(filter_mode="host", join_ndv_limit=2).run(q)
        np.testing.assert_array_equal(rep.scan_sets["e"].part_ids,
                                      host.scan_sets["e"].part_ids)

    def test_float_key_bloom_summaries_fall_back_to_host(self):
        """A float probe key domain is ineligible for the integer
        enumeration kernel: the Bloom path must keep the host matcher,
        counted under join_bloom, with identical scan sets."""
        rng = np.random.default_rng(9)
        probe = Table.build(
            "fp", {"k": rng.uniform(0, 100, 400)}, rows_per_partition=4)
        build = Table.build(
            "bld", {"k": rng.uniform(0, 100, 64)}, rows_per_partition=8)
        q = Query(scans={"p": TableScanSpec(probe),
                         "b": TableScanSpec(build)},
                  join=JoinSpec("b", "p", "k", "k"))
        svc = PruningService(mode="ref")
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=4)
        rep = svc.run_batch([q], pipe)[0]
        assert rep.per_scan["p"]["join"].detail["summary_kind"] == "bloom"
        assert rep.per_scan["p"]["join"].detail["path"] == "host"
        assert svc.counters.technique["join_bloom"]["fallbacks"] == 1
        assert svc.counters.technique["join_bloom"]["launches"] == 0
        host = PruningPipeline(filter_mode="host", join_ndv_limit=4).run(q)
        np.testing.assert_array_equal(rep.scan_sets["p"].part_ids,
                                      host.scan_sets["p"].part_ids)


def _bloom_mixed_workload(events, users, rng, n=24):
    """Joins whose build NDV straddles a small ndv_limit: grp-filtered
    builds (~50 ids) summarize as Bloom, id-capped builds (<= 6 ids) as
    distinct — plus plain filter queries (run with join_ndv_limit=8)."""
    qs = []
    for i in range(n):
        lo = int(rng.integers(0, 900_000))
        pred = (E.col("ts") >= lo) & (E.col("ts") <= lo + 150_000)
        g = int(rng.integers(0, 8))
        kind = i % 3
        if kind == 0:
            qs.append(Query(scans={"e": TableScanSpec(events, pred)}))
        elif kind == 1:   # Bloom summary: ~400/8 distinct build ids > 8
            qs.append(Query(
                scans={"e": TableScanSpec(events, pred),
                       "u": TableScanSpec(users, E.col("grp") == g)},
                join=JoinSpec("u", "e", "id", "uid")))
        else:             # distinct summary: <= 6 build ids
            qs.append(Query(
                scans={"e": TableScanSpec(events, pred),
                       "u": TableScanSpec(users, E.col("id") <= 5)},
                join=JoinSpec("u", "e", "id", "uid")))
    return qs


class TestBloomEngineParity:
    def test_mixed_distinct_bloom_batched_parity_and_launch_bounds(self):
        """The ISSUE 3 acceptance shape: a mixed distinct/Bloom workload
        where run_batch == per-query device == host pipeline, with one
        distinct launch and one Bloom launch per (table, key col) group
        and zero host fallbacks."""
        events, users = _engine_tables(seed=23)
        rng = np.random.default_rng(24)
        queries = _bloom_mixed_workload(events, users, rng, n=24)
        svc = PruningService(mode="ref")
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=8)
        before = svc.counters.snapshot()
        batch = svc.run_batch(queries, pipe)
        after = svc.counters.snapshot()
        seq = [pipe.run(q) for q in queries]
        for b, s in zip(batch, seq):
            _assert_reports_equal(b, s)
        host = PruningPipeline(filter_mode="host", join_ndv_limit=8)
        for q, b in zip(queries, batch):
            h = host.run(q)
            for name in h.scan_sets:
                np.testing.assert_array_equal(b.scan_sets[name].part_ids,
                                              h.scan_sets[name].part_ids)
        kinds = {b.per_scan["e"]["join"].detail["summary_kind"]
                 for b in batch if "join" in b.per_scan.get("e", {})}
        assert kinds == {"distinct", "bloom"}
        delta = {t: {f: after["technique"][t][f]
                     - before["technique"].get(t, dict(launches=0,
                                                       fallbacks=0))[f]
                     for f in ("launches", "fallbacks")}
                 for t in after["technique"]}
        assert delta["join"] == dict(launches=1, fallbacks=0)
        assert delta["join_bloom"] == dict(launches=1, fallbacks=0)

    def test_interpret_mode_engine_matches_ref(self):
        """The Pallas kernel (interpret) drives the same engine results
        as the jnp/numpy ref backend on a Bloom workload."""
        events, users = _engine_tables(seed=25)
        rng = np.random.default_rng(26)
        queries = [q for q in _bloom_mixed_workload(events, users, rng, n=6)
                   if q.join is not None]
        out = {}
        for mode in ("ref", "interpret"):
            svc = PruningService(mode=mode)
            pipe = PruningPipeline(filter_mode="device", service=svc,
                                   join_ndv_limit=8)
            out[mode] = svc.run_batch(queries, pipe)
        for a, b in zip(out["ref"], out["interpret"]):
            for name in a.scan_sets:
                np.testing.assert_array_equal(a.scan_sets[name].part_ids,
                                              b.scan_sets[name].part_ids)

    @settings(max_examples=25, deadline=None)
    @given(
        build=st.lists(st.one_of(st.integers(0, 300),
                                 st.floats(0, 300, allow_nan=False)),
                       min_size=5, max_size=60),
        probe_seed=st.integers(0, 2**31),
        float_probe=st.booleans(),
    )
    def test_device_bloom_never_prunes_joinable(self, build, probe_seed,
                                                float_probe):
        """Sec. 6.2 guarantee through the device path, integer and float
        probe domains, fractional build keys included: a partition
        containing a joinable key is never pruned, and on integer domains
        the device result is bit-identical to the host matcher."""
        rng = np.random.default_rng(probe_seed)
        vals = (rng.uniform(0, 300, 160) if float_probe
                else rng.integers(0, 300, 160).astype(np.int64))
        probe = Table.build("p", {"k": vals}, rows_per_partition=4)
        build_keys = np.asarray(build, dtype=np.float64)
        summary = summarize_build(build_keys, ndv_limit=0)  # force Bloom
        assert summary.bloom is not None
        svc = PruningService(mode="ref")
        scan = ScanSet.full(probe.num_partitions)
        hit = svc.join_hit(probe, "k", summary, part_ids=scan.part_ids)
        bh = None if hit is None else np.asarray(hit)[scan.part_ids] > 0
        res = prune_probe(scan, probe.stats, "k", summary, bloom_hit=bh)
        host = prune_probe(ScanSet.full(probe.num_partitions), probe.stats,
                           "k", summary)
        np.testing.assert_array_equal(res.scan.part_ids,
                                      host.scan.part_ids)
        kept = set(res.scan.part_ids.tolist())
        for p in range(probe.num_partitions):
            v, _ = probe.partition_ctx(p).col("k")
            if np.isin(v, build_keys).any():
                assert p in kept, f"pruned joinable partition {p}"

    def test_filter_built_only_when_a_matcher_reads_it(self, monkeypatch):
        """One batch mixing a wide-key Bloom join (keys over 2^33, as
        TPC-H's order keys: no partition is enumerable and the host
        matcher keeps it) with narrow-key Bloom joins (one device launch):
        the wide filter is never built, the launched ones are, and the
        scan sets equal those of the same batch with every filter built
        at once."""
        from repro.core import flow
        events, users = _engine_tables(seed=27)
        rng = np.random.default_rng(28)
        okeys = rng.integers(0, 2**33, 2048)
        lineitem = Table.build("lineitem", {"okey": okeys},
                               rows_per_partition=64)
        orders = Table.build("orders", {
            "okey": np.sort(okeys[::2]),
            "flag": rng.integers(0, 4, 1024).astype(np.int64),
        }, rows_per_partition=64)
        wide = Query(scans={"l": TableScanSpec(lineitem),
                            "o": TableScanSpec(orders, E.col("flag") <= 2)},
                     join=JoinSpec("o", "l", "okey", "okey"))
        queries = [wide] + _bloom_mixed_workload(events, users, rng, n=6)

        def run():
            svc = PruningService(mode="ref")
            pipe = PruningPipeline(filter_mode="device", service=svc,
                                   join_ndv_limit=8)
            return svc, svc.run_batch(queries, pipe)

        svc, lazy = run()
        orig = flow.summarize_build

        def eager(*a, **kw):
            summary = orig(*a, **kw)
            summary.bloom                     # build the filter at once
            return summary

        monkeypatch.setattr(flow, "summarize_build", eager)
        svc_eager, forced = run()

        joins = [(r.per_scan["l"]["join"] if "l" in r.per_scan
                  else r.per_scan["e"].get("join")) for r in lazy]
        assert joins[0].detail["path"] == "host"
        assert joins[0].detail["bloom"] == "deferred"
        launched = [j for j in joins[1:]
                    if j is not None and j.detail["summary_kind"] == "bloom"]
        assert len(launched) == 2
        assert all(j.detail["path"] == "device"
                   and j.detail["bloom"] == "built" for j in launched)
        assert svc.counters.technique["join_bloom"] == dict(
            launches=1, fallbacks=1, built=2, deferred=1)
        assert svc_eager.counters.technique["join_bloom"] == dict(
            launches=1, fallbacks=1, built=3, deferred=0)
        for a, b in zip(lazy, forced):
            for name in a.scan_sets:
                np.testing.assert_array_equal(a.scan_sets[name].part_ids,
                                              b.scan_sets[name].part_ids)
                np.testing.assert_array_equal(a.scan_sets[name].match,
                                              b.scan_sets[name].match)
                ja = a.per_scan[name].get("join")
                jb = b.per_scan[name].get("join")
                assert (ja is None) == (jb is None)
                if ja is not None:
                    assert ({k: v for k, v in ja.detail.items()
                             if k != "bloom"}
                            == {k: v for k, v in jb.detail.items()
                                if k != "bloom"})


# ---------------------------------------------------------------------------
# DML invalidation of the runtime-technique planes
# ---------------------------------------------------------------------------

class TestPlaneInvalidation:
    def _service_with_staged_planes(self):
        events, users = _engine_tables(seed=9)
        # verdict cache off: these tests pin exact flat plane-staging
        # miss counts, which verdict-plane misses would perturb
        svc = PruningService(mode="ref", verdict_cache=False)
        pipe = PruningPipeline(filter_mode="device", service=svc)
        rng = np.random.default_rng(10)
        svc.run_batch(_mixed_workload(events, users, rng, n=8), pipe)
        return svc, pipe, events, users

    def test_update_on_join_key_restages_plane(self):
        svc, pipe, events, users = self._service_with_staged_planes()
        misses = svc.cache.plane_misses
        rng = np.random.default_rng(11)
        work = _mixed_workload(events, users, rng, n=8)
        svc.run_batch(work, pipe)
        assert svc.cache.plane_misses == misses      # planes resident
        svc.notify_update("events", "uid")           # the join key column
        svc.run_batch(work, pipe)
        assert svc.cache.plane_misses == misses + 1  # key plane re-staged

    def test_update_on_order_column_restages_topk_plane(self):
        svc, pipe, events, users = self._service_with_staged_planes()
        n_topk = len(svc.cache.topk_planes)
        assert n_topk >= 1
        svc.notify_update("events", "val")           # the order column
        assert len(svc.cache.topk_planes) == 0
        rng = np.random.default_rng(12)
        misses = svc.cache.plane_misses
        svc.run_batch(_mixed_workload(events, users, rng, n=8), pipe)
        assert svc.cache.plane_misses > misses

    def test_wrong_column_update_keeps_planes(self):
        """An update to an unrelated column must NOT re-stage the join-key
        or block-top-k planes (it cannot change their values) — while the
        [C, P] min/max planes do re-stage (they carry every column)."""
        svc, pipe, events, users = self._service_with_staged_planes()
        key_planes = dict(svc.cache.key_planes)
        topk_planes = dict(svc.cache.topk_planes)
        stat_misses = svc.cache.misses
        svc.notify_update("events", "ts")            # neither key nor order
        assert dict(svc.cache.key_planes) == key_planes
        assert dict(svc.cache.topk_planes) == topk_planes
        rng = np.random.default_rng(13)
        misses = svc.cache.plane_misses
        svc.run_batch(_mixed_workload(events, users, rng, n=8), pipe)
        assert svc.cache.plane_misses == misses      # planes survived
        assert svc.cache.misses > stat_misses        # min/max re-staged

    def test_insert_and_delete_drop_all_planes(self):
        svc, pipe, events, users = self._service_with_staged_planes()
        assert svc.cache.key_planes and svc.cache.topk_planes
        svc.notify_insert("events", 2)
        assert not any(k[0] == "events" for k in svc.cache.key_planes)
        assert not any(k[0] == "events" for k in svc.cache.topk_planes)
        svc2, _, ev2, us2 = self._service_with_staged_planes()
        svc2.notify_delete("events")
        assert not any(k[0] == "events" for k in svc2.cache.topk_planes)

    def test_enum_plane_column_granular_invalidation(self):
        """The enumeration plane follows the join-key plane's DML
        discipline: a key-column update re-stages it, an unrelated-column
        update keeps it resident, insert/delete drop it."""
        events, users = _engine_tables(seed=27)
        # verdict cache off: the test counts enum-plane misses exactly
        svc = PruningService(mode="ref", verdict_cache=False)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=8)
        rng = np.random.default_rng(28)
        work = _bloom_mixed_workload(events, users, rng, n=9)
        svc.run_batch(work, pipe)
        assert any(k[0] == "events" and k[2] == "uid"
                   for k in svc.cache.enum_planes)
        misses = svc.cache.plane_misses
        svc.run_batch(work, pipe)
        assert svc.cache.plane_misses == misses      # plane resident
        svc.notify_update("events", "ts")            # unrelated column
        svc.run_batch(work, pipe)
        assert svc.cache.plane_misses == misses      # still resident
        svc.notify_update("events", "uid")           # the join key column
        assert not any(k[0] == "events" and k[2] == "uid"
                       for k in svc.cache.enum_planes)
        svc.run_batch(work, pipe)
        assert svc.cache.plane_misses > misses       # re-staged
        svc.notify_insert("events", 1)
        assert not any(k[0] == "events" for k in svc.cache.enum_planes)

    def test_enum_plane_guards_non_enumerable_rows(self):
        """Width rows are 0 (= keep, never prune) wherever enumeration
        would be unsound: empty intervals and out-of-int32 bounds."""
        cache = DeviceStatsCache()
        big = np.array([0, 1, 2**40, 2**40 + 1, 5, 6], dtype=np.int64)
        t = Table.build("t", {"k": big}, rows_per_partition=2,
                        nulls={"k": np.array([0, 0, 0, 0, 1, 1], bool)})
        pmin, width, wmax, domain_ok = cache.enum_plane(t, "k")
        width = np.asarray(width)
        assert width[1] == 0                 # 2**40 range: outside int32
        assert width[2] == 0                 # all-null partition: empty
        assert width[0] == 2 and wmax == 2   # [0, 1] enumerates fine
        assert not domain_ok                 # a live partition exceeds int32
        small = Table.build("s", {"k": np.arange(8, dtype=np.int64)},
                            rows_per_partition=4)
        assert cache.enum_plane(small, "k")[3]

    def test_rebuilt_table_never_hits_stale_plane(self):
        """Same name + shape, new data: stats.uid keying must re-stage
        (a stale block-top-k plane would fabricate a boundary witness)."""
        cache = DeviceStatsCache()
        t1 = Table.build("t", {"v": np.arange(100, dtype=np.int64)},
                         rows_per_partition=10)
        p1 = cache.block_topk_plane(t1, "v", True)
        t2 = Table.build("t", {"v": np.arange(500, 600, dtype=np.int64)},
                         rows_per_partition=10)
        p2 = cache.block_topk_plane(t2, "v", True)
        assert float(np.asarray(p2).max()) == 599.0
        assert cache.plane_misses == 2 and p1 is not p2


# ---------------------------------------------------------------------------
# PruningReport.overall_ratio guard (satellite)
# ---------------------------------------------------------------------------

class TestOverallRatioGuard:
    def _report(self, scan_ids, skipped, topk_scan="e"):
        tbl = Table.build("t", {"v": np.arange(100, dtype=np.int64)},
                          rows_per_partition=10)           # 10 partitions
        res = TopKResult(values=np.zeros(1), scanned=np.zeros(0, np.int64),
                         skipped=np.asarray(skipped, dtype=np.int64),
                         pruning_ratio=0.0, rows_scanned=0,
                         boundary_final=0.0)
        rep = PruningReport(
            per_scan={"e": {}},
            scan_sets={"e": ScanSet(np.asarray(scan_ids, dtype=np.int64))},
            topk=res, topk_scan=topk_scan)
        rep._scan_specs = {"e": TableScanSpec(tbl)}
        return rep

    def test_skipped_partitions_present_are_subtracted(self):
        rep = self._report(scan_ids=[0, 1, 2, 3], skipped=[2, 3])
        # 10 total, 4 remaining - 2 skipped = 2 -> ratio 0.8
        assert rep.overall_ratio == pytest.approx(0.8)

    def test_skipped_partitions_already_removed_not_double_subtracted(self):
        """Regression: skipped partitions already gone from scan_sets must
        not be subtracted again (the old code could push remaining
        negative and the ratio past 1.0)."""
        rep = self._report(scan_ids=[0, 1], skipped=[2, 3])
        assert rep.overall_ratio == pytest.approx(0.8)     # not 1.0+
        rep2 = self._report(scan_ids=[0, 1, 2], skipped=[2, 3])
        assert rep2.overall_ratio == pytest.approx(0.8)    # only #2 present
        assert 0.0 <= rep2.overall_ratio <= 1.0

    def test_legacy_report_without_target_scan_stays_guarded(self):
        """topk_scan=None (reports built outside the engine): the guard
        still applies per single scan — table-local partition ids from
        other scans must not satisfy the presence check."""
        rep = self._report(scan_ids=[0, 1, 2, 3], skipped=[2, 3],
                           topk_scan=None)
        assert rep.overall_ratio == pytest.approx(0.8)
        rep2 = self._report(scan_ids=[0, 1], skipped=[2, 3], topk_scan=None)
        assert rep2.overall_ratio == pytest.approx(0.8)    # none present
        assert 0.0 <= rep2.overall_ratio <= 1.0

    def test_engine_reports_stay_in_range(self):
        events, users = _engine_tables(seed=15)
        rng = np.random.default_rng(16)
        for q in _mixed_workload(events, users, rng, n=12):
            r = PruningPipeline().run(q)
            assert 0.0 <= r.overall_ratio <= 1.0


# ---------------------------------------------------------------------------
# benchmark smoke (satellite)
# ---------------------------------------------------------------------------

class TestBenchSmoke:
    def test_runtime_prune_bench_runs(self, tmp_path):
        from benchmarks.bench_runtime_prune import run
        json_path = str(tmp_path / "BENCH_runtime_prune.json")
        rows, cells = run(grid_p=(512,), grid_q=(8,), json_path=json_path)
        assert len(cells) == 1
        assert cells[0]["launches"]["filter"]["launches"] >= 1
        import json as _json
        with open(json_path) as f:
            payload = _json.load(f)
        assert payload["bench"] == "runtime_prune"
        assert len(payload["grid"]) == 1
        # Bloom cell: batched enumeration launches, no host fallbacks
        assert payload["bloom"]["bloom_launches"] >= 1
        assert payload["bloom"]["bloom_fallbacks"] == 0
        assert "bloom_qps_delta" in payload["acceptance"]
