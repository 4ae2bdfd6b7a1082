"""Chaos suite: the pruning service fails prune-less, never wrong.

The resilience layer (PR 6) turns pruning's safe-degraded-answer
property into machinery: a ``DegradationLadder`` that demotes a failing
launch down an ordered rung chain (sharded device -> device -> host
kernel -> host oracle -> no-prune passthrough), a checksum-stamped
plane-integrity protocol in ``DeviceStatsCache``, and a ``FaultInjector``
seam threaded through staging / eviction / getters / launches.  This
suite pins three contracts:

  * **never raise**: ``run_batch`` / ``run_fleet`` return a report per
    query under any injected fault schedule (errors, delays, torn
    planes, eviction faults) interleaved with DML and budget pressure;
  * **never wrong**: every scan set is a superset of the host oracle's
    (a kept partition is always safe), and is *bit-identical* whenever
    the ladder stopped at or above the host-oracle rung (no
    passthroughs, no isolated query errors in the batch's counters);
  * **deterministic timing**: retry/backoff/deadline arithmetic runs
    under an injectable clock — no test ever really sleeps.

Plus the two satellite regressions: ``pin_scope`` exception safety
(zero leaked pins even when eviction callbacks raise mid-cleanup) and
per-query error isolation of malformed specs.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import expr as E
from repro.core.device_stats import (DeviceStatsCache, PlaneIntegrityError,
                                     plane_checksum)
from repro.core.flow import PruningPipeline, Query, TableScanSpec
from repro.serve.prune_service import PruningService
from repro.serve.resilience import (RUNGS, BackoffPolicy, DegradationLadder,
                                    FaultInjector, InjectedFault,
                                    new_resilience_counters)

from test_fleet_parity import (NDV_LIMIT, _plane_mesh_or_none, _rows,
                               assert_reports_equal, build_fleet,
                               fleet_queries, measure_working_set)

NO_SLEEP = lambda d: None  # noqa: E731


class FakeClock:
    """Monotonic clock + sleep pair: sleeping advances the clock."""

    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def __call__(self):
        return self.t

    def sleep(self, d):
        self.sleeps.append(d)
        self.t += d


def _filter_query(t, lo=0, hi=400):
    return Query(scans={t.name: TableScanSpec(
        t, (E.col("v") >= lo) & (E.col("v") <= hi))})


def assert_scan_superset(qs, got, want, label):
    """Every kept partition of the oracle is kept by the resilient run."""
    for qi, (a, b) in enumerate(zip(got, want)):
        for name in qs[qi].scans:
            dropped = np.setdiff1d(b.scan_sets[name].part_ids,
                                   a.scan_sets[name].part_ids)
            assert dropped.size == 0, \
                f"{label}: q={qi} scan={name} lost partitions {dropped}"


def assert_scan_parity(qs, got, want, label):
    """ids + three-valued match bit-identical (the exact-rung promise)."""
    for qi, (a, b) in enumerate(zip(got, want)):
        for name in qs[qi].scans:
            np.testing.assert_array_equal(
                a.scan_sets[name].part_ids, b.scan_sets[name].part_ids,
                err_msg=f"{label}: q={qi} scan={name} part_ids")
            np.testing.assert_array_equal(
                a.scan_sets[name].match, b.scan_sets[name].match,
                err_msg=f"{label}: q={qi} scan={name} match")


def _apply_dml(op, tables, rng):
    t = tables[int(rng.integers(0, len(tables)))]
    if op == "append":
        n = int(rng.integers(4, 16))
        t.append_partitions(_rows(rng, n),
                            nulls={"v": rng.random(n) < 0.08},
                            rows_per_partition=4)
    elif op == "drop":
        live = np.where(t.live_mask)[0]
        if live.size > 2:
            t.drop_partitions(rng.choice(live, size=1))
    elif op == "rewrite":
        live = np.where(t.live_mask)[0]
        pid = int(live[rng.integers(0, live.size)])
        n = int(np.diff(t.part_bounds)[pid])
        t.rewrite_partitions([pid], _rows(rng, n))
    elif op == "update":
        t.update_column("g", rng.integers(0, 40, t.num_rows)
                        .astype(np.int64))


# ---------------------------------------------------------------------------
# BackoffPolicy: deterministic exponential schedule, no real time involved
# ---------------------------------------------------------------------------

class TestBackoffPolicy:
    def test_exponential_growth_then_cap(self):
        import random
        p = BackoffPolicy(base_delay=0.01, multiplier=2.0, max_delay=0.05)
        rng = random.Random(0)
        delays = [p.delay(i, rng) for i in range(5)]
        assert delays == [0.01, 0.02, 0.04, 0.05, 0.05]

    def test_jitter_bounded_and_deterministic_under_seed(self):
        import random
        p = BackoffPolicy(base_delay=0.01, multiplier=2.0, max_delay=1.0,
                          jitter=0.5)
        a = [p.delay(i, random.Random(7)) for i in range(6)]
        b = [p.delay(i, random.Random(7)) for i in range(6)]
        assert a == b, "same seed must replay the same jittered schedule"
        for i, d in enumerate(a):
            base = 0.01 * 2.0 ** i
            assert base <= d <= min(base * 1.5, 1.0) + 1e-12

    def test_jitter_never_exceeds_cap(self):
        import random
        p = BackoffPolicy(base_delay=0.2, multiplier=2.0, max_delay=0.25,
                          jitter=1.0)
        rng = random.Random(3)
        assert all(p.delay(i, rng) <= 0.25 for i in range(10))


# ---------------------------------------------------------------------------
# DegradationLadder: retry counts, demotion attribution, deadlines
# ---------------------------------------------------------------------------

class TestDegradationLadder:
    def _ladder(self, **kw):
        clock = FakeClock()
        c = new_resilience_counters()
        lad = DegradationLadder(clock=clock, sleep=clock.sleep,
                                counters=c, **kw)
        return lad, clock, c

    def test_first_rung_success_touches_nothing(self):
        lad, clock, c = self._ladder()
        result, rung = lad.execute([("sharded", lambda: 42),
                                    ("device", lambda: 0)])
        assert (result, rung) == (42, "sharded")
        assert c["retries"] == 0 and c["deadline_hits"] == 0
        assert not any(c["demotions"].values()) and not clock.sleeps

    def test_retries_then_demotes_with_backoff_sleeps(self):
        lad, clock, c = self._ladder(
            policy=BackoffPolicy(retries=2, base_delay=1.0, multiplier=2.0,
                                 max_delay=8.0))

        def bad():
            raise RuntimeError("kernel down")

        result, rung = lad.execute([("device", bad), ("host_kernel",
                                                      lambda: "host")])
        assert (result, rung) == ("host", "host_kernel")
        assert c["retries"] == 2           # two re-attempts on the rung
        assert clock.sleeps == [1.0, 2.0]  # deterministic exponential
        assert c["demotions"] == {"sharded_tree": 0, "tree": 0, "sharded": 0,
                                  "device": 0, "host_kernel": 1,
                                  "host_oracle": 0, "passthrough": 0}

    def test_deadline_refuses_to_sleep_into_expiry(self):
        # base delay alone exceeds the stage deadline: abandon the rung
        # (one deadline hit) without sleeping rather than sleeping past it
        lad, clock, c = self._ladder(
            policy=BackoffPolicy(retries=5, base_delay=10.0,
                                 max_delay=10.0),
            deadline_s=5.0)

        def bad():
            raise RuntimeError("down")

        result, rung = lad.execute([("device", bad), ("host_kernel",
                                                      lambda: 1)])
        assert rung == "host_kernel"
        assert c["deadline_hits"] == 1 and c["retries"] == 0
        assert clock.sleeps == []

    def test_deadline_expired_during_attempt(self):
        lad, clock, c = self._ladder(
            policy=BackoffPolicy(retries=5, base_delay=0.001),
            deadline_s=2.0)

        def slow_and_bad():
            clock.t += 3.0                  # the attempt itself blew it
            raise RuntimeError("slow")

        _, rung = lad.execute([("device", slow_and_bad),
                               ("host_oracle", lambda: 1)])
        assert rung == "host_oracle"
        assert c["deadline_hits"] == 1 and c["retries"] == 0

    def test_passthrough_counted(self):
        lad, _clock, c = self._ladder(policy=BackoffPolicy(retries=0))

        def bad():
            raise RuntimeError("down")

        _, rung = lad.execute([("device", bad), ("host_kernel", bad),
                               ("host_oracle", bad),
                               ("passthrough", lambda: None)])
        assert rung == "passthrough"
        assert c["passthroughs"] == 1
        assert c["demotions"]["host_kernel"] == 1
        assert c["demotions"]["host_oracle"] == 1
        assert c["demotions"]["passthrough"] == 1

    def test_all_rungs_failing_raises_last(self):
        lad, _clock, _c = self._ladder(policy=BackoffPolicy(retries=0))

        def bad():
            raise KeyError("no safe bottom")

        with pytest.raises(KeyError):
            lad.execute([("device", bad), ("host_kernel", bad)])

    def test_last_errors_name_each_demoted_rung(self):
        lad, _clock, _c = self._ladder(policy=BackoffPolicy(retries=0))
        first, second = RuntimeError("compile refused"), KeyError("torn")

        def raise_(exc):
            def thunk():
                raise exc
            return thunk

        _, rung = lad.execute([("tree", raise_(first)),
                               ("device", raise_(second)),
                               ("host_kernel", lambda: 1)])
        assert rung == "host_kernel"
        assert lad.last_errors == {"tree": first, "device": second}
        lad.execute([("device", lambda: 2)])     # success keeps the record
        assert lad.last_errors["device"] is second

    def test_rung_order_matches_contract(self):
        assert RUNGS == ("verdict", "sharded_tree", "tree", "sharded",
                         "device", "host_kernel", "host_oracle",
                         "passthrough")


# ---------------------------------------------------------------------------
# FaultInjector: named sites, seeded schedules, torn-plane corruption
# ---------------------------------------------------------------------------

class TestFaultInjector:
    def test_prefix_and_exact_site_matching(self):
        inj = FaultInjector()
        inj.add("launch.filter")
        with pytest.raises(InjectedFault):
            inj.fire("launch.filter:sharded")     # prefix match
        with pytest.raises(InjectedFault):
            inj.fire("launch.filter")             # exact match
        inj.fire("launch.join:device")            # different site: silent
        inj.fire("stage.stat")

    def test_after_and_times_schedule(self):
        inj = FaultInjector()
        inj.add("get.stat", after=1, times=2)
        inj.fire("get.stat")                      # skipped (after=1)
        with pytest.raises(InjectedFault):
            inj.fire("get.stat")                  # fires 1/2
        with pytest.raises(InjectedFault):
            inj.fire("get.stat")                  # fires 2/2
        inj.fire("get.stat")                      # exhausted
        assert len(inj.log) == 2

    def test_prob_schedule_replays_under_fixed_seed(self):
        def run(seed):
            inj = FaultInjector(seed=seed)
            inj.add("evict", prob=0.5)
            hits = []
            for i in range(30):
                try:
                    inj.fire("evict")
                    hits.append(0)
                except InjectedFault:
                    hits.append(1)
            return hits

        assert run(11) == run(11), "fixed seed must replay the schedule"
        assert 0 < sum(run(11)) < 30

    def test_delay_kind_uses_injected_sleep(self):
        slept = []
        inj = FaultInjector(sleep=slept.append)
        inj.add("launch.topk", kind="delay", delay=0.5, times=2)
        inj.fire("launch.topk:device")
        inj.fire("launch.topk:device")
        assert slept == [0.5, 0.5]

    def test_custom_exception(self):
        inj = FaultInjector()
        inj.add("stage.stat", exc=TimeoutError("hbm"))
        with pytest.raises(TimeoutError):
            inj.fire("stage.stat")

    def test_corrupt_tears_bytes_but_keeps_shape(self):
        inj = FaultInjector(seed=0)
        inj.add("stage.stat", kind="corrupt", times=1)
        arrays = (np.arange(12, dtype=np.float32).reshape(3, 4),
                  np.arange(6, dtype=np.int8))
        stamp = plane_checksum(arrays)
        torn = inj.corrupt("stage.stat", arrays)
        assert all(a.shape == b.shape and a.dtype == b.dtype
                   for a, b in zip(arrays, torn))
        assert plane_checksum(torn) != stamp
        # schedule exhausted: next call passes arrays through untouched
        again = inj.corrupt("stage.stat", arrays)
        assert plane_checksum(again) == stamp

    def test_disabled_rules_do_not_match_other_kinds(self):
        inj = FaultInjector()
        inj.add("stage.stat", kind="corrupt")
        inj.fire("stage.stat")        # corrupt rules never raise via fire
        out = inj.corrupt("stage.join_key", (np.zeros(3),))
        assert plane_checksum(out) == plane_checksum((np.zeros(3),))


# ---------------------------------------------------------------------------
# Satellite 1: pin_scope exception safety
# ---------------------------------------------------------------------------

class TestPinScopeExceptionSafety:
    def test_body_exception_unpins_everything(self):
        tables, _dim = build_fleet(1, seed=5)
        a = tables[0]
        cache = DeviceStatsCache(budget_bytes=1 << 20)
        with pytest.raises(RuntimeError, match="boom"):
            with cache.pin_scope():
                cache.get(a)
                cache.join_key_plane(a, "k")
                raise RuntimeError("boom")
        assert cache.memory.pinned_bytes == 0
        assert cache.memory.bytes_in_use == cache.resident_bytes

    def test_unpin_failure_still_unpins_the_rest_and_reraises(self):
        """One raising unpin must not strand the frame's other pins."""
        tables, _dim = build_fleet(1, seed=6)
        a = tables[0]
        cache = DeviceStatsCache(budget_bytes=1 << 20)
        mgr = cache.memory
        orig = mgr.unpin
        tripped = []

        def flaky_unpin(family, key):
            orig(family, key)           # the pin itself is released...
            if not tripped:
                tripped.append(1)
                raise RuntimeError("cb")  # ...then bookkeeping blows up

        mgr.unpin = flaky_unpin
        try:
            with pytest.raises(RuntimeError, match="cb"):
                with cache.pin_scope():
                    cache.get(a)
                    cache.join_key_plane(a, "k")
                    cache.block_topk_plane(a, "v", True)
        finally:
            mgr.unpin = orig
        assert mgr.pinned_bytes == 0, "a raising unpin leaked other pins"
        assert not mgr._orphan_pins

    def test_eviction_fault_during_scope_exit_leaks_no_pins(self):
        """reclaim() at scope exit hits an eviction fault: the exception
        propagates, but every pin was already released and the cache /
        manager accounting agree (store entry popped before the fault
        seam fires)."""
        tables, _dim = build_fleet(2, seed=7)
        a, b = tables
        inj = FaultInjector()
        cache = DeviceStatsCache(fault_injector=inj)
        cache.get(a)
        cache.get(b)
        cache.memory.budget_bytes = cache.resident_bytes - 1  # must evict
        inj.add("evict", times=1)
        with pytest.raises(InjectedFault):
            with cache.pin_scope():
                cache.get(a)               # pin a; b is the LRU victim
        assert cache.memory.pinned_bytes == 0
        assert cache.memory.bytes_in_use == cache.resident_bytes
        # the cache recovers: next reclaim (no fault left) gets under
        # budget and serving continues
        cache.memory.reclaim()
        assert cache.memory.bytes_in_use <= cache.memory.budget_bytes
        cache.get(a)

    def test_nested_scope_inner_exception_spares_outer_pins(self):
        tables, _dim = build_fleet(1, seed=8)
        a = tables[0]
        cache = DeviceStatsCache(budget_bytes=1 << 20)
        key = (a.name, a.stats.uid)
        with cache.pin_scope():
            cache.get(a)
            with pytest.raises(RuntimeError):
                with cache.pin_scope():
                    cache.join_key_plane(a, "k")
                    raise RuntimeError("inner")
            # outer frame's pin still held: the stat plane can't evict
            assert cache.memory._resident[("stat", key)].pins == 1
        assert cache.memory.pinned_bytes == 0


# ---------------------------------------------------------------------------
# Plane integrity: stamp, sampled verify, quarantine, forced restage
# ---------------------------------------------------------------------------

class TestPlaneIntegrity:
    def test_clean_planes_verify_clean(self):
        tables, dim = build_fleet(2, seed=9)
        svc = PruningService(mode="ref", integrity_sample=1)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        qs = fleet_queries(tables, dim, np.random.default_rng(0), 12)
        svc.run_batch(qs, pipe)
        integ = svc.cache.integrity
        assert integ["verifications"] > 0
        assert integ["checksum_failures"] == 0
        assert integ["quarantines"] == 0

    def test_torn_stage_quarantined_then_serves_truth(self):
        """One corrupt staging: the sampled verifier catches it before
        the first verdict, quarantines, and the forced restage serves
        the oracle's answer — a counter, not a wrong prune."""
        tables, _dim = build_fleet(1, seed=10)
        a = tables[0]
        inj = FaultInjector(seed=1)
        inj.add("stage.stat", kind="corrupt", times=1)
        svc = PruningService(mode="ref", fault_injector=inj,
                             integrity_sample=1)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        qs = [_filter_query(a)]
        got = svc.run_batch(qs, pipe)
        assert_reports_equal(qs, got, [host.run(q) for q in qs], "torn")
        integ = svc.cache.integrity
        assert integ["checksum_failures"] == 1
        assert integ["quarantines"] == 1
        assert got[0].counters["integrity"]["quarantines"] == 1
        # resilience untouched: integrity healed below the ladder
        assert not any(got[0].counters["resilience"]["demotions"].values())

    def test_persistent_corruption_demotes_never_raises(self):
        """Every restage torn: the integrity protocol raises
        PlaneIntegrityError internally, the ladder demotes past the
        device rungs, and the batch still returns the exact answer."""
        tables, _dim = build_fleet(1, seed=11)
        a = tables[0]
        inj = FaultInjector(seed=2)
        inj.add("stage.stat", kind="corrupt")        # no times cap
        svc = PruningService(mode="ref", fault_injector=inj,
                             integrity_sample=1, sleep=NO_SLEEP)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        qs = [_filter_query(a)]
        got = svc.run_batch(qs, pipe)
        assert_reports_equal(qs, got, [host.run(q) for q in qs],
                             "persistent-corruption")
        res = got[0].counters["resilience"]
        assert res["demotions"]["host_kernel"] >= 1
        assert res["passthroughs"] == 0
        assert svc.cache.integrity["quarantines"] >= 2

    def test_restage_after_eviction_always_verified(self):
        tables, _dim = build_fleet(2, seed=12)
        a, b = tables
        # default sampling (64): the forced check is what must fire
        cache = DeviceStatsCache()
        cache.get(a)
        cache.memory.budget_bytes = cache.resident_bytes
        cache.get(b)                                  # evicts a
        assert cache.memory.was_evicted("stat", (a.name, a.stats.uid))
        before = cache.integrity["verifications"]
        cache.memory.budget_bytes = None
        cache.get(a)                                  # restage: forced verify
        assert cache.integrity["verifications"] == before + 1
        assert cache.integrity["checksum_failures"] == 0

    def test_direct_checksum_roundtrip(self):
        arrays = (np.arange(10, dtype=np.float32), np.ones(4, np.int8))
        assert plane_checksum(arrays) == plane_checksum(
            tuple(np.array(a, copy=True) for a in arrays))
        other = (np.arange(10, dtype=np.float32) + 1, np.ones(4, np.int8))
        assert plane_checksum(arrays) != plane_checksum(other)


# ---------------------------------------------------------------------------
# Satellite 2: malformed queries isolate, the batch survives
# ---------------------------------------------------------------------------

class TestQueryErrorIsolation:
    def test_bad_column_is_isolated_to_passthrough(self):
        tables, _dim = build_fleet(2, seed=13)
        a, b = tables
        svc = PruningService(mode="ref")
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        good = _filter_query(a)
        bad = Query(scans={b.name: TableScanSpec(b, E.col("nope") > 3)})
        reps = svc.run_batch([good, bad, _filter_query(b, 100, 700)], pipe)
        assert len(reps) == 3
        res = reps[0].counters["resilience"]
        assert res["errors"] == 1
        # the malformed query degraded to keep-everything, PARTIAL only
        ss = reps[1].scan_sets[b.name]
        live = np.where(b.live_mask)[0]
        np.testing.assert_array_equal(np.sort(ss.part_ids), live)
        assert set(np.unique(ss.match)) == {1}, \
            "passthrough must never certify FULL"
        # its neighbours still get exact verdicts
        assert_reports_equal([good], [reps[0]], [host.run(good)], "q0")
        q2 = _filter_query(b, 100, 700)
        assert_reports_equal([q2], [reps[2]], [host.run(q2)], "q2")

    def test_bad_order_by_column_isolated(self):
        tables, _dim = build_fleet(1, seed=14)
        a = tables[0]
        svc = PruningService(mode="ref")
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        bad = Query(scans={a.name: TableScanSpec(a, E.col("v") >= 0)},
                    limit=3, order_by=(a.name, "missing", True))
        reps = svc.run_batch([bad], pipe)
        assert reps[0].counters["resilience"]["errors"] == 1
        assert set(np.unique(reps[0].scan_sets[a.name].match)) <= {1}


# ---------------------------------------------------------------------------
# Ladder end-to-end on the real service
# ---------------------------------------------------------------------------

class TestServiceDegradation:
    def test_device_launch_faults_demote_exactly(self):
        """Device launches down, host kernel up: answers bit-identical,
        demotions attributed to the host_kernel rung."""
        tables, dim = build_fleet(2, seed=15)
        inj = FaultInjector()
        inj.add("launch.filter:device")
        inj.add("launch.filter:sharded")
        svc = PruningService(mode="ref", fault_injector=inj,
                             sleep=NO_SLEEP)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        qs = [_filter_query(tables[0]), _filter_query(tables[1], -50, 300)]
        got = svc.run_batch(qs, pipe)
        assert_reports_equal(qs, got, [host.run(q) for q in qs],
                             "device-down")
        res = got[0].counters["resilience"]
        assert res["demotions"]["host_kernel"] >= 1
        assert res["passthroughs"] == 0 and res["errors"] == 0

    def test_total_filter_blackout_passes_through_supersets(self):
        tables, _dim = build_fleet(1, seed=16)
        a = tables[0]
        inj = FaultInjector()
        inj.add("launch.filter")          # every rung with a launch site
        svc = PruningService(mode="ref", fault_injector=inj,
                             sleep=NO_SLEEP)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        qs = [_filter_query(a)]
        got = svc.run_batch(qs, pipe)     # must not raise
        res = got[0].counters["resilience"]
        assert res["passthroughs"] >= 1
        assert res["demotions"]["passthrough"] >= 1
        assert_scan_superset(qs, got, [host.run(q) for q in qs],
                             "blackout")
        ss = got[0].scan_sets[a.name]
        assert set(np.unique(ss.match)) == {1}

    def test_join_and_topk_degrade_to_exact_host(self):
        tables, dim = build_fleet(2, seed=17)
        inj = FaultInjector()
        inj.add("launch.join")
        inj.add("launch.topk")
        svc = PruningService(mode="ref", fault_injector=inj,
                             sleep=NO_SLEEP)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        rng = np.random.default_rng(3)
        qs = fleet_queries(tables, dim, rng, 16)
        got = svc.run_batch(qs, pipe)
        # join/topk host-oracle rungs are exact: scan parity holds
        assert_scan_parity(qs, got, [host.run(q) for q in qs],
                           "join-topk-down")
        res = got[0].counters["resilience"]
        assert res["passthroughs"] == 0 and res["errors"] == 0

    def test_retry_heals_transient_fault_without_demotion(self):
        tables, _dim = build_fleet(1, seed=18)
        a = tables[0]
        inj = FaultInjector()
        inj.add("launch.filter:device", times=1)   # one transient blip
        svc = PruningService(mode="ref", fault_injector=inj,
                             backoff=BackoffPolicy(retries=1,
                                                   base_delay=0.0),
                             sleep=NO_SLEEP)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        qs = [_filter_query(a)]
        got = svc.run_batch(qs, pipe)
        assert_reports_equal(qs, got, [host.run(q) for q in qs], "blip")
        res = got[0].counters["resilience"]
        assert res["retries"] == 1
        assert not any(res["demotions"].values())

    def test_fleet_summary_carries_resilience_and_integrity(self):
        tables, _dim = build_fleet(1, seed=19)
        inj = FaultInjector()
        inj.add("launch.filter:device", times=1)
        svc = PruningService(mode="ref", fault_injector=inj,
                             backoff=BackoffPolicy(retries=0),
                             sleep=NO_SLEEP)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        svc.run_batch([_filter_query(tables[0])], pipe)
        summary = svc.fleet_summary()
        assert summary["resilience"]["demotions"]["host_kernel"] == 1
        assert "verifications" in summary["integrity"]


# ---------------------------------------------------------------------------
# The chaos harness: randomized fault schedules x DML x budget pressure
# ---------------------------------------------------------------------------

SITES = ("launch.filter:sharded", "launch.filter:device", "launch.filter",
         "launch.join", "launch.join_bloom", "launch.topk",
         "stage.stat", "stage.join_key", "stage.enum", "stage.block_topk",
         "get.stat", "get.join_key", "get.block_topk", "evict")
CORRUPT_SITES = ("stage.stat", "stage.join_key", "stage.block_topk")


@st.composite
def fault_plans(draw):
    rules = []
    for _ in range(draw(st.integers(1, 4))):
        kind = draw(st.sampled_from(["error", "error", "corrupt", "delay"]))
        site = draw(st.sampled_from(
            CORRUPT_SITES if kind == "corrupt" else SITES))
        rules.append(dict(
            site=site, kind=kind,
            prob=draw(st.sampled_from([1.0, 0.5, 0.25])),
            times=draw(st.sampled_from([1, 3, None])),
            after=draw(st.integers(0, 2)),
            delay=0.001 if kind == "delay" else 0.0))
    return rules


class TestChaosHarness:
    """Fault schedules interleaved with DML + budget pressure: never
    raise, never smaller than the oracle's kept set, bit-identical when
    the ladder never fell below the host-oracle rung."""

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 2 ** 31),
           plan=fault_plans(),
           dml=st.lists(st.sampled_from(
               ["append", "drop", "rewrite", "update", "noop"]),
               min_size=1, max_size=3),
           budget_frac=st.sampled_from([None, 0.35, 0.6]))
    def test_chaos_rounds(self, seed, plan, dml, budget_frac):
        rng = np.random.default_rng(seed)
        tables, dim = build_fleet(3, seed)
        budget = None
        if budget_frac is not None:
            budget = max(1, int(measure_working_set(tables, dim)
                                * budget_frac))
        inj = FaultInjector(seed=seed, sleep=NO_SLEEP)
        for rule in plan:
            inj.add(**rule)
        svc = PruningService(mode="ref", budget_bytes=budget,
                             shard_mesh=_plane_mesh_or_none(),
                             fault_injector=inj, sleep=NO_SLEEP,
                             integrity_sample=1)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        for rnd, op in enumerate(["noop"] + list(dml)):
            _apply_dml(op, tables, rng)
            qs = fleet_queries(tables, dim, rng, 8)
            got = svc.run_batch(qs, pipe)          # the never-raise claim
            assert len(got) == len(qs)
            want = [host.run(q) for q in qs]
            label = f"round {rnd} ({op})"
            assert_scan_superset(qs, got, want, label)
            res = got[0].counters["resilience"]
            if res["passthroughs"] == 0 and res["errors"] == 0:
                # every rung at or above host_oracle is exact
                assert_scan_parity(qs, got, want, label)
            mem = svc.cache.memory
            assert mem.pinned_bytes == 0
            assert mem.bytes_in_use == svc.cache.resident_bytes

    def test_run_fleet_survives_blackout_storm(self):
        """A fixed worst-case schedule through run_fleet: launches
        erroring, stages torn, evictions faulting, under a budget that
        forces churn — every round returns, every set is a superset."""
        tables, dim = build_fleet(3, seed=23)
        budget = max(1, int(measure_working_set(tables, dim) * 0.4))
        inj = FaultInjector(seed=5, sleep=NO_SLEEP)
        inj.add("launch.filter", prob=0.5)
        inj.add("launch.join", prob=0.5)
        inj.add("launch.topk", prob=0.5)
        inj.add("stage.stat", kind="corrupt", prob=0.3)
        inj.add("evict", prob=0.25)
        inj.add("get.join_key", prob=0.3)
        svc = PruningService(mode="ref", budget_bytes=budget,
                             fault_injector=inj, sleep=NO_SLEEP,
                             integrity_sample=1)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        rng = np.random.default_rng(23)
        batches = [fleet_queries(tables, dim, rng, 8) for _ in range(3)]
        rounds = svc.run_fleet(batches, pipe)      # must not raise
        assert len(rounds) == len(batches)
        for rnd, (qs, got) in enumerate(zip(batches, rounds)):
            want = [host.run(q) for q in qs]
            assert_scan_superset(qs, got, want, f"fleet round {rnd}")
        assert svc.cache.memory.pinned_bytes == 0

    def test_no_faults_means_no_resilience_activity(self):
        """The ladder + integrity machinery is pure bookkeeping when
        nothing fails: zero demotions, zero retries, zero passthroughs,
        zero checksum failures — the <5% overhead bench's precondition."""
        tables, dim = build_fleet(2, seed=29)
        svc = PruningService(mode="ref")
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        rng = np.random.default_rng(4)
        for _ in range(2):
            got = svc.run_batch(fleet_queries(tables, dim, rng, 10), pipe)
            res = got[0].counters["resilience"]
            assert res["retries"] == 0 and res["passthroughs"] == 0
            assert not any(res["demotions"].values())
        assert svc.cache.integrity["checksum_failures"] == 0
