"""Fleet-scale parity: the memory-budgeted, partition-sharded plane.

The paper's 99.4% pruning win assumes min/max metadata stays *always hot*
across a fleet of thousands of tables — which only works if residency is
bounded.  This suite pins the contract of the ``PlaneMemoryManager`` +
sharded launch path:

  * **golden parity**: over many-table workloads with skewed table
    popularity and interleaved DML, the budgeted + partition-sharded
    engine's output is bit-identical to the unbounded unsharded engine
    and to the f64 host oracle, for every technique;
  * **eviction invariants**: pinned planes are never evicted mid-launch,
    the budget is never exceeded (except counter-pinned), and a
    re-staged evicted plane serves the table's *current* state — then
    resumes delta-replaying its log;
  * **atomicity**: getters' epoch check + plane read cannot race DML
    invalidation under the eviction path (the satellite-4 regression).

Sharded cases need a multi-device CPU mesh (tests/conftest.py forces 8
host devices; REPRO_CPU_DEVICES=0 opts out and the sharded cases skip).
"""

import threading

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro.core import expr as E
from repro.core.device_stats import PlaneMemoryManager
from repro.core.flow import JoinSpec, PruningPipeline, Query, TableScanSpec
from repro.data.table import Table
from repro.serve.prune_service import PruningService

NDV_LIMIT = 8      # straddled by build sides: small -> distinct, big -> Bloom


def _plane_mesh_or_none():
    if len(jax.devices()) < 2:
        return None
    from repro.kernels.ops import make_plane_mesh
    return make_plane_mesh()


def _rows(rng, n):
    return {
        "k": rng.integers(0, 60, n).astype(np.int64),
        "v": rng.integers(-200, 1000, n).astype(np.int64),
        "g": rng.integers(0, 50, n).astype(np.int64),
    }


def build_fleet(n_tables, seed, rows=48, rows_per_partition=4):
    """``n_tables`` small fact tables + one shared dimension table."""
    rng = np.random.default_rng(seed)
    tables = [
        Table.build(f"t{i:03d}", _rows(rng, rows),
                    rows_per_partition=rows_per_partition,
                    nulls={"v": rng.random(rows) < 0.08})
        for i in range(n_tables)
    ]
    dim = Table.build("dim", {
        "a": rng.integers(0, 100, 40).astype(np.int64),
        "k": rng.integers(0, 60, 40).astype(np.int64),
    }, rows_per_partition=8)
    return tables, dim


def _zipf_weights(n, s=1.2):
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def fleet_queries(tables, dim, rng, n_queries):
    """Skewed-popularity workload mixing every technique family."""
    weights = _zipf_weights(len(tables))
    qs = []
    for _ in range(n_queries):
        t = tables[int(rng.choice(len(tables), p=weights))]
        lo = int(rng.integers(-100, 800))
        kind = int(rng.integers(0, 6))
        if kind == 0:      # filter (device fast path)
            qs.append(Query(scans={t.name: TableScanSpec(
                t, (E.col("v") >= lo) & (E.col("v") <= lo + 300))}))
        elif kind == 1:    # filter with NOT -> host-fallback shape
            qs.append(Query(scans={t.name: TableScanSpec(
                t, E.Not(E.col("v") > lo) | (E.col("g") == 7))}))
        elif kind == 2:    # plain LIMIT
            qs.append(Query(scans={t.name: TableScanSpec(
                t, E.col("v") >= lo)}, limit=int(rng.integers(1, 10))))
        elif kind == 3:    # top-k (block-top-k plane)
            qs.append(Query(scans={t.name: TableScanSpec(
                t, E.col("v") >= -150)}, limit=int(rng.integers(1, 6)),
                order_by=(t.name, "v", bool(rng.integers(0, 2)))))
        elif kind == 4:    # join, small build -> distinct summary
            a_lo = int(rng.integers(0, 85))
            qs.append(Query(
                scans={t.name: TableScanSpec(t),
                       "dim": TableScanSpec(dim, (E.col("a") >= a_lo)
                                            & (E.col("a") <= a_lo + 8))},
                join=JoinSpec("dim", t.name, "k", "k")))
        else:              # join, full build -> Bloom summary
            qs.append(Query(
                scans={t.name: TableScanSpec(t, E.col("v") >= lo - 300),
                       "dim": TableScanSpec(dim)},
                join=JoinSpec("dim", t.name, "k", "k")))
    return qs


def warm_queries(tables, dim):
    """One query per technique per table: stages every plane family —
    the unbounded working set whose resident bytes size the budget."""
    qs = []
    for t in tables:
        qs.append(Query(scans={t.name: TableScanSpec(
            t, (E.col("v") >= 0) & (E.col("v") <= 500))}))
        qs.append(Query(scans={t.name: TableScanSpec(t, E.col("v") >= -150)},
                        limit=3, order_by=(t.name, "v", True)))
        qs.append(Query(
            scans={t.name: TableScanSpec(t), "dim": TableScanSpec(dim)},
            join=JoinSpec("dim", t.name, "k", "k")))
    return qs


def measure_working_set(tables, dim):
    """Resident bytes after an unbounded warm pass over every table."""
    svc = PruningService(mode="ref")
    pipe = PruningPipeline(filter_mode="device", service=svc,
                           join_ndv_limit=NDV_LIMIT)
    svc.run_batch(warm_queries(tables, dim), pipe)
    return svc.cache.resident_bytes


def assert_reports_equal(qs, got, want, label):
    for qi, (a, b) in enumerate(zip(got, want)):
        for name in qs[qi].scans:
            np.testing.assert_array_equal(
                a.scan_sets[name].part_ids, b.scan_sets[name].part_ids,
                err_msg=f"{label}: q={qi} scan={name} part_ids")
            np.testing.assert_array_equal(
                a.scan_sets[name].match, b.scan_sets[name].match,
                err_msg=f"{label}: q={qi} scan={name} match")
        assert (a.topk is None) == (b.topk is None), \
            f"{label}: q={qi} topk presence differs"
        if a.topk is not None:
            np.testing.assert_array_equal(a.topk.values, b.topk.values,
                                          err_msg=f"{label}: q={qi} topk")
            np.testing.assert_array_equal(a.topk.skipped, b.topk.skipped,
                                          err_msg=f"{label}: q={qi} skipped")


class TestGoldenFleetParity:
    """budgeted + sharded == unbounded unsharded == host oracle."""

    def test_acceptance_64_tables_25pct_budget(self):
        """The PR's acceptance cell: 64 tables, budget = 25% of the
        working set, skewed popularity — outputs bit-identical, the
        memory counters show evictions, and the budget holds."""
        tables, dim = build_fleet(64, seed=11)
        ws = measure_working_set(tables, dim)
        budget = int(ws * 0.25)
        mesh = _plane_mesh_or_none()

        unbounded = PruningService(mode="ref")
        pipe_u = PruningPipeline(filter_mode="device", service=unbounded,
                                 join_ndv_limit=NDV_LIMIT)
        budgeted = PruningService(mode="ref", budget_bytes=budget,
                                  shard_mesh=mesh)
        pipe_b = PruningPipeline(filter_mode="device", service=budgeted,
                                 join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)

        rng = np.random.default_rng(5)
        # Round 0 sweeps every table (the fleet's full working set — 4x
        # the budget, so the LRU must churn), then skewed rounds model
        # the shifting-popularity steady state.
        batches = [warm_queries(tables, dim)] + [
            fleet_queries(tables, dim, rng, 16) for _ in range(2)]
        reps_b = budgeted.run_fleet(batches, pipe_b)
        reps_u = unbounded.run_fleet(batches, pipe_u)
        for rnd, (qs, rb, ru) in enumerate(zip(batches, reps_b, reps_u)):
            assert_reports_equal(qs, rb, ru,
                                 f"round {rnd} budgeted-vs-unbounded")
            rh = [host.run(q) for q in qs]
            assert_reports_equal(qs, rb, rh, f"round {rnd} budgeted-vs-host")

        mem = budgeted.cache.memory
        assert mem.evictions > 0, "25% budget over 64 tables must evict"
        assert mem.peak_bytes <= budget, "budget exceeded"
        assert mem.over_budget_events == 0 and mem.pin_denied == 0
        assert mem.bytes_in_use == budgeted.cache.resident_bytes
        # the per-batch report counters surface the same story
        last = reps_b[-1][0].counters["memory"]
        assert last["budget_bytes"] == budget
        assert last["bytes_in_use"] <= budget
        if mesh is not None:
            assert budgeted.counters.sharded_launches > 0
            assert unbounded.counters.sharded_launches == 0

    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 2 ** 31),
           n_tables=st.integers(3, 6),
           budget_frac=st.sampled_from([0.2, 0.35, 0.5]),
           dml=st.lists(st.sampled_from(
               ["append", "drop", "rewrite", "update"]),
               min_size=1, max_size=3))
    def test_skewed_workload_with_dml(self, seed, n_tables, budget_frac,
                                      dml):
        """Rounds of skewed queries with DML interleaved: parity holds
        whether a touched table's planes were delta-synced (resident) or
        re-staged from scratch (evicted)."""
        rng = np.random.default_rng(seed)
        tables, dim = build_fleet(n_tables, seed)
        ws = measure_working_set(tables, dim)
        budget = max(1, int(ws * budget_frac))
        mesh = _plane_mesh_or_none()

        budgeted = PruningService(mode="ref", budget_bytes=budget,
                                  shard_mesh=mesh)
        pipe_b = PruningPipeline(filter_mode="device", service=budgeted,
                                 join_ndv_limit=NDV_LIMIT)
        unbounded = PruningService(mode="ref")
        pipe_u = PruningPipeline(filter_mode="device", service=unbounded,
                                 join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)

        for rnd, op in enumerate(["noop"] + list(dml)):
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
            qs = fleet_queries(tables, dim, rng, 10)
            rb = budgeted.run_batch(qs, pipe_b)
            ru = unbounded.run_batch(qs, pipe_u)
            rh = [host.run(q) for q in qs]
            assert_reports_equal(qs, rb, ru,
                                 f"round {rnd} ({op}) budgeted-vs-unbounded")
            assert_reports_equal(qs, rb, rh,
                                 f"round {rnd} ({op}) budgeted-vs-host")
            mem = budgeted.cache.memory
            assert mem.bytes_in_use == budgeted.cache.resident_bytes
            assert mem.peak_bytes <= budget or mem.over_budget_events > 0


class TestEvictionInvariants:
    def test_manager_never_evicts_pinned(self):
        mgr = PlaneMemoryManager(budget_bytes=100)
        evicted = []
        mgr.bind(lambda fam, key: evicted.append((fam, key)))
        mgr.admit("stat", ("a",), 60)
        assert mgr.pin("stat", ("a",))
        mgr.admit("stat", ("b",), 60)       # only unpinned candidate is b's
        assert ("stat", ("a",)) not in evicted
        assert mgr.pin_denied == 1 and mgr.over_budget_events == 1
        assert mgr.bytes_in_use == 120      # pinned overflow, accounted
        mgr.unpin("stat", ("a",))
        mgr.admit("stat", ("c",), 50)       # now a (LRU) and b both go
        assert evicted == [("stat", ("a",)), ("stat", ("b",))]
        assert mgr.bytes_in_use == 50 <= 100
        assert mgr.evictions == 2

    def test_restage_storm_counter(self):
        mgr = PlaneMemoryManager(budget_bytes=100)
        mgr.bind(lambda fam, key: None)
        mgr.admit("stat", ("a",), 80)
        mgr.admit("stat", ("b",), 80)       # evicts a
        assert mgr.restage_storms == 0
        mgr.admit("stat", ("a",), 80)       # a returns: thrash
        assert mgr.restage_storms == 1

    def test_unbudgeted_manager_never_evicts(self):
        mgr = PlaneMemoryManager()
        mgr.bind(lambda fam, key: pytest.fail("evicted without a budget"))
        for i in range(50):
            mgr.admit("stat", (i,), 1 << 20)
        assert mgr.evictions == 0
        assert mgr.bytes_in_use == 50 << 20

    def test_pinned_planes_survive_launch_pressure(self):
        """A plane acquired inside a pin scope stays resident while the
        scope is open even when admitting another table would otherwise
        evict it — and goes first once the scope closes."""
        tables, dim = build_fleet(2, seed=3)
        a, b = tables
        # verdict-cache off: a repeat of q(b) must re-stage b's stat
        # plane (a verdict hit would serve without touching the budget)
        svc = PruningService(mode="ref", verdict_cache=False)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        q = lambda t: Query(scans={t.name: TableScanSpec(  # noqa: E731
            t, (E.col("v") >= 0) & (E.col("v") <= 400))})
        svc.run_batch([q(a)], pipe)
        a_bytes = svc.cache.resident_bytes
        svc.cache.memory.budget_bytes = int(a_bytes * 1.5)  # < a + b

        key_a = (a.name, a.stats.uid)
        with svc.cache.pin_scope():
            svc.cache.get(a)                 # pin a's stat plane
            svc.run_batch([q(b)], pipe)      # b's staging wants a's bytes
            assert key_a in svc.cache.entries, "pinned plane evicted"
            assert svc.cache.memory.pin_denied >= 1
        svc.run_batch([q(b), q(b)], pipe)    # scope closed: a is fair game
        assert key_a not in svc.cache.entries
        assert svc.cache.memory.evictions >= 1
        mem = svc.cache.memory
        assert mem.bytes_in_use == svc.cache.resident_bytes

    def test_evicted_plane_restages_current_state_then_deltas(self):
        """An evicted plane must come back reflecting the table's current
        version (DML that happened while it was cold included), and the
        delta log must resume replaying afterwards — never stale bounds,
        never a permanent full-restage regime."""
        tables, dim = build_fleet(2, seed=7)
        a, b = tables
        rng = np.random.default_rng(7)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)

        svc = PruningService(mode="ref")
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        q = lambda t, lo: Query(scans={t.name: TableScanSpec(  # noqa: E731
            t, (E.col("v") >= lo) & (E.col("v") <= lo + 350))})
        svc.run_batch([q(a, 0)], pipe)
        a_bytes = svc.cache.resident_bytes
        svc.cache.memory.budget_bytes = int(a_bytes * 1.5)

        svc.run_batch([q(b, 0)], pipe)       # evicts a's planes
        assert (a.name, a.stats.uid) not in svc.cache.entries
        # DML lands while a is cold
        a.append_partitions(_rows(rng, 8), rows_per_partition=4)
        a.drop_partitions([1])
        qs = [q(a, 100)]
        got = svc.run_batch(qs, pipe)
        assert_reports_equal(qs, got, [host.run(qq) for qq in qs],
                             "post-eviction restage")
        assert svc.cache.memory.restage_storms >= 1
        # With pressure off (the appended partitions grew a's plane past
        # the old budget), the re-staged plane resumes delta-replaying
        # its log: the next append is O(ΔP), never a full restage.
        svc.cache.memory.budget_bytes = None
        svc.run_batch(qs, pipe)                  # ensure resident
        a.append_partitions(_rows(rng, 4), rows_per_partition=4)
        staging = svc.run_batch(qs, pipe)[0].counters["staging"]
        assert staging["full_restages"] == 0
        assert staging["delta_stages"] >= 1

    def test_nested_equal_pin_scopes_unwind_by_identity(self):
        """A nested scope whose frame is equal-by-content to the outer
        one (same single plane pinned) must pop ITS OWN frame — an
        equality-based removal popped the outer frame instead, leaked
        its pins forever, and raised on the outer exit."""
        tables, _dim = build_fleet(1, seed=4)
        a = tables[0]
        svc = PruningService(mode="ref", budget_bytes=1 << 20)
        cache = svc.cache
        with cache.pin_scope():
            cache.get(a)
            with cache.pin_scope():
                cache.get(a)             # frame == outer frame by content
            cache.get(a)                 # must land in the OUTER frame
        assert cache.memory.pinned_bytes == 0
        key = (a.name, a.stats.uid)
        assert cache.memory._resident[("stat", key)].pins == 0

    def test_oversized_plane_counts_over_budget_not_pin_denied(self):
        """A plane larger than the whole budget is an over-budget event,
        not pin pressure — and admitting it neither flushes the rest of
        the fleet (pointless) nor survives the next reclaim."""
        mgr = PlaneMemoryManager(budget_bytes=100)
        evicted = []
        mgr.bind(lambda fam, key: evicted.append(key))
        mgr.admit("stat", ("a",), 40)
        mgr.admit("stat", ("b",), 40)
        mgr.admit("stat", ("huge",), 150)
        assert mgr.over_budget_events == 1 and mgr.pin_denied == 0
        assert evicted == []                 # no collateral fleet flush
        mgr.reclaim()                        # pin-scope exit
        assert evicted == [("huge",)]        # the unfittable plane goes first
        assert mgr.bytes_in_use == 80

    def test_release_parks_pins_as_debt(self):
        """An invalidate that drops a pinned record must not let the
        pinning scope's later unpin strip a DIFFERENT scope's pin on a
        re-admitted record under the same key (which would allow a
        mid-launch eviction)."""
        mgr = PlaneMemoryManager(budget_bytes=100)
        mgr.bind(lambda fam, key: None)
        mgr.admit("stat", ("x",), 10)
        assert mgr.pin("stat", ("x",))          # scope A pins
        mgr.release("stat", ("x",))             # DML invalidate mid-scope
        mgr.admit("stat", ("x",), 10)           # scope B restages...
        assert mgr.pin("stat", ("x",))          # ...and pins the fresh record
        mgr.unpin("stat", ("x",))               # scope A exits: consumes debt
        assert mgr._resident[("stat", ("x",))].pins == 1   # B's pin intact
        mgr.unpin("stat", ("x",))               # scope B exits
        assert mgr._resident[("stat", ("x",))].pins == 0
        assert not mgr._orphan_pins

    def test_flow_rejects_budget_args_with_explicit_service(self):
        svc = PruningService(mode="ref")
        with pytest.raises(ValueError):
            PruningPipeline(filter_mode="device", service=svc,
                            budget_bytes=1 << 20)
        with pytest.raises(ValueError):
            PruningPipeline(filter_mode="device", service=svc,
                            shard_planes=True)

    def test_budget_counter_pinned_in_reports(self):
        """counters['memory'] carries the per-batch delta + gauges."""
        tables, dim = build_fleet(6, seed=9)
        ws = measure_working_set(tables, dim)
        svc = PruningService(mode="ref", budget_bytes=int(ws * 0.3))
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        rng = np.random.default_rng(2)
        last = None
        for _ in range(3):
            last = svc.run_batch(fleet_queries(tables, dim, rng, 12), pipe)
        mem = last[0].counters["memory"]
        for k in PlaneMemoryManager.MONOTONIC + PlaneMemoryManager.GAUGES:
            assert k in mem
        assert mem["budget_bytes"] == int(ws * 0.3)
        assert mem["bytes_in_use"] <= mem["budget_bytes"]
        assert svc.cache.memory.evictions > 0


class TestGetterAtomicity:
    """Satellite 4: epoch check + plane read are atomic per getter."""

    def test_concurrent_getters_vs_invalidation(self):
        tables, dim = build_fleet(3, seed=13)
        svc = PruningService(mode="ref", budget_bytes=1 << 20)
        cache = svc.cache
        errors = []
        stop = threading.Event()

        def reader(t):
            try:
                while not stop.is_set():
                    e = cache.get(t)
                    # the read the epoch check must cover: a stale entry
                    # handed out mid-invalidate would mix versions
                    assert e.mins.shape[0] == len(t.stats.columns)
                    cache.join_key_plane(t, "k")
                    cache.block_topk_plane(t, "v", True)
            except Exception as exc:        # pragma: no cover - regression
                errors.append(exc)

        def invalidator():
            try:
                for i in range(200):
                    cache.on_update(tables[i % 3].name, "v")
                    cache.invalidate(tables[(i + 1) % 3].name)
            except Exception as exc:        # pragma: no cover - regression
                errors.append(exc)
            finally:
                stop.set()

        threads = [threading.Thread(target=reader, args=(t,))
                   for t in tables for _ in range(2)]
        threads.append(threading.Thread(target=invalidator))
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not errors, errors
        # accounting stayed atomic: manager bytes == store truth
        assert cache.memory.bytes_in_use == cache.resident_bytes
        assert cache.memory.pinned_bytes == 0


class TestShardedLaunches:
    def test_sharded_engine_runs_and_counts(self):
        mesh = _plane_mesh_or_none()
        if mesh is None:
            pytest.skip("needs >= 2 host devices (REPRO_CPU_DEVICES)")
        tables, dim = build_fleet(3, seed=21)
        svc = PruningService(mode="ref", shard_mesh=mesh)
        pipe = PruningPipeline(filter_mode="device", service=svc,
                               join_ndv_limit=NDV_LIMIT)
        rng = np.random.default_rng(0)
        qs = fleet_queries(tables, dim, rng, 16) + warm_queries(tables, dim)
        reps = svc.run_batch(qs, pipe)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        assert_reports_equal(qs, reps, [host.run(q) for q in qs],
                             "sharded-vs-host")
        assert svc.counters.sharded_launches > 0
        assert reps[0].counters["sharded_launches"] > 0

    def test_flow_level_budget_and_shard_args(self):
        """PruningPipeline builds its lazy service budgeted + sharded."""
        tables, dim = build_fleet(2, seed=22)
        pipe = PruningPipeline(filter_mode="device", budget_bytes=1 << 20,
                               shard_planes=len(jax.devices()) > 1,
                               join_ndv_limit=NDV_LIMIT)
        host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
        rng = np.random.default_rng(1)
        for q in fleet_queries(tables, dim, rng, 8):
            got = pipe.run(q)
            want = host.run(q)
            assert_reports_equal([q], [got], [want], "flow-level")
        svc = pipe.device_service()
        assert svc.cache.memory.budget_bytes == 1 << 20
        if len(jax.devices()) > 1:
            assert svc.counters.sharded_launches > 0
