"""Async serving front-end (PR 10): admission, micro-batching, SLOs.

Contracts pinned here:

  * **scheduling is deterministic under an injected clock** — inline
    (``threaded=False``) mode dispatches on the size cap at submit time
    and on the deadline at ``poll()`` time, per the FakeClock, with no
    real sleeps anywhere;
  * **the front-end adds scheduling, never semantics** — batched results
    are bit-identical to calling ``run_batch`` directly on the same
    queries (the acceptance parity);
  * **observability** — every response carries its queue/stage/launch
    timestamps, every report carries a ``counters["latency"]`` block
    whose keys are all declared in ``COUNTER_REGISTRY`` (CL006), and
    ``fleet_summary()["latency"]`` accumulates the lifetime view;
  * **staging overlap** — ``prestage``/``prefetch`` stage a cold
    table's planes ahead of the launch (counted in ``prefetch_stages``)
    and the launch then stages nothing new.
"""

import threading

import numpy as np
import pytest

from repro.core import expr as E
from repro.core.flow import PruningPipeline, Query, TableScanSpec
from repro.data.table import Table
from repro.serve.frontend import FrontendResponse, ServingFrontend
from repro.serve.prune_service import (LADDER_LAUNCH_SITES, PruningService)
from repro.serve.resilience import COUNTER_REGISTRY, new_latency_counters

from test_fleet_parity import (assert_reports_equal, build_fleet,
                               fleet_queries)


class FakeClock:
    """Monotonic clock whose time only moves when the test advances it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, d):
        self.t += d


def small_table(name="fe_t", rows=240, seed=5):
    rng = np.random.default_rng(seed)
    return Table.build(name, {
        "ts": np.sort(rng.integers(0, 10_000, rows)).astype(np.int64),
        "v": rng.integers(0, 1_000, rows).astype(np.int64),
    }, rows_per_partition=8)


def window_query(table, lo, width=2_000):
    return Query(scans={table.name: TableScanSpec(
        table, (E.col("ts") >= int(lo)) & (E.col("ts") <= int(lo + width)))})


def make_frontend(max_batch=4, deadline_s=1.0, clock=None, threaded=False,
                  prefetch=True):
    svc = PruningService(mode="ref", verdict_cache=False)
    pipe = PruningPipeline(filter_mode="device", service=svc)
    fe = ServingFrontend(svc, pipe, max_batch=max_batch,
                         deadline_s=deadline_s, clock=clock,
                         threaded=threaded, prefetch=prefetch)
    return svc, pipe, fe


class TestScheduling:
    """Inline mode + FakeClock: dispatch causes are fully deterministic."""

    def test_size_cap_fires_at_submit(self):
        clock = FakeClock()
        t = small_table()
        _svc, _pipe, fe = make_frontend(max_batch=3, deadline_s=5.0,
                                        clock=clock)
        futs = [fe.submit(window_query(t, 100 * i)) for i in range(3)]
        # the third submit filled the cap: all three resolved inline,
        # with zero clock movement (the deadline never came into it)
        assert all(f.done() for f in futs)
        assert [f.result().cause for f in futs] == ["size"] * 3
        assert clock.t == 0.0

    def test_deadline_fires_at_poll(self):
        clock = FakeClock()
        t = small_table()
        _svc, _pipe, fe = make_frontend(max_batch=8, deadline_s=5.0,
                                        clock=clock)
        futs = [fe.submit(window_query(t, 100 * i)) for i in range(2)]
        assert not any(f.done() for f in futs)
        assert fe.poll() is None            # deadline not reached yet
        clock.advance(4.999)
        assert fe.poll() is None
        clock.advance(0.001)
        assert fe.poll() == "deadline"      # T since the oldest submit
        assert [f.result().cause for f in futs] == ["deadline"] * 2

    def test_deadline_anchored_to_oldest_submission(self):
        clock = FakeClock()
        t = small_table()
        _svc, _pipe, fe = make_frontend(max_batch=8, deadline_s=5.0,
                                        clock=clock)
        fe.submit(window_query(t, 0))
        clock.advance(4.0)
        late = fe.submit(window_query(t, 500))
        clock.advance(1.0)                  # oldest is now 5.0s old
        assert fe.poll() == "deadline"
        # the late submission rode along instead of waiting its own T
        assert late.result().cause == "deadline"

    def test_flush_dispatches_partial_batch(self):
        clock = FakeClock()
        t = small_table()
        _svc, _pipe, fe = make_frontend(max_batch=8, deadline_s=5.0,
                                        clock=clock)
        futs = [fe.submit(window_query(t, 100 * i)) for i in range(2)]
        assert fe.flush() == 2
        assert [f.result().cause for f in futs] == ["flush", "flush"]
        assert fe.flush() == 0              # nothing pending: no-op

    def test_close_flushes_and_rejects_new_submits(self):
        clock = FakeClock()
        t = small_table()
        _svc, _pipe, fe = make_frontend(max_batch=8, deadline_s=5.0,
                                        clock=clock)
        fut = fe.submit(window_query(t, 0))
        fe.close()
        assert fut.result().cause == "flush"
        with pytest.raises(RuntimeError, match="closed"):
            fe.submit(window_query(t, 100))

    def test_oversize_burst_splits_into_capped_batches(self):
        clock = FakeClock()
        t = small_table()
        svc, _pipe, fe = make_frontend(max_batch=2, deadline_s=5.0,
                                       clock=clock)
        futs = [fe.submit(window_query(t, 100 * i)) for i in range(5)]
        assert [f.done() for f in futs] == [True] * 4 + [False]
        fe.flush()
        assert svc.latency["batches"] == 3
        assert svc.latency["size_fired"] == 2
        assert svc.latency["flush_fired"] == 1


class TestParity:
    """Acceptance: frontend-batched results bit-identical to run_batch."""

    def test_frontend_bit_identical_to_direct_run_batch(self):
        tables, dim = build_fleet(6, seed=29)
        rng = np.random.default_rng(29)
        qs = fleet_queries(tables, dim, rng, 24)
        direct_svc = PruningService(mode="ref", verdict_cache=False)
        direct_pipe = PruningPipeline(filter_mode="device",
                                      service=direct_svc)
        want = direct_svc.run_batch(qs, direct_pipe)

        clock = FakeClock()
        _svc, _pipe, fe = make_frontend(max_batch=len(qs), deadline_s=60.0,
                                        clock=clock)
        futs = [fe.submit(q) for q in qs]    # last submit fills the cap
        fe.close()
        got = [f.result().report for f in futs]
        assert_reports_equal(qs, got, want, "frontend vs run_batch")

    def test_parity_survives_micro_batch_splits(self):
        """Splitting the workload into deadline/size micro-batches must
        not change any answer (run_batch is batch-size invariant)."""
        tables, dim = build_fleet(4, seed=31)
        rng = np.random.default_rng(31)
        qs = fleet_queries(tables, dim, rng, 10)
        direct_svc = PruningService(mode="ref", verdict_cache=False)
        want = direct_svc.run_batch(
            qs, PruningPipeline(filter_mode="device", service=direct_svc))

        clock = FakeClock()
        _svc, _pipe, fe = make_frontend(max_batch=3, deadline_s=2.0,
                                        clock=clock)
        futs = []
        for q in qs:                         # 3 size batches + 1 flush
            futs.append(fe.submit(q))
        fe.close()
        got = [f.result().report for f in futs]
        assert_reports_equal(qs, got, want, "micro-batched vs run_batch")


class TestObservability:
    def test_response_timestamps_and_latency_block(self):
        clock = FakeClock()
        t = small_table()
        svc, _pipe, fe = make_frontend(max_batch=8, deadline_s=5.0,
                                       clock=clock)
        fe.submit(window_query(t, 0))
        clock.advance(2.0)
        fut = fe.submit(window_query(t, 300))
        clock.advance(3.0)
        assert fe.poll() == "deadline"
        resp = fut.result()
        assert isinstance(resp, FrontendResponse)
        ts = resp.timestamps
        assert ts["queued"] == 2.0           # clock units, per FakeClock
        assert ts["queued"] <= ts["dispatched"] <= ts["launched"] \
            <= ts["done"]
        assert ts["staged"] is not None      # inline prestage ran
        assert resp.queue_ms == pytest.approx(3_000.0)
        assert resp.latency_ms >= resp.queue_ms
        assert resp.queue_depth == 2
        block = resp.report.counters["latency"]
        assert block["requests"] == 2 and block["deadline_fired"] == 1
        assert set(block) == set(new_latency_counters())
        # lifetime view surfaces through fleet_summary()
        summary = svc.fleet_summary()["latency"]
        assert summary["requests"] == 2 and summary["batches"] == 1
        assert summary["queue_depth_peak"] == 2

    def test_latency_counter_keys_all_registered(self):
        """CL006 satellite: every key the front-end emits — the factory
        family, the per-batch block, and the section name itself — is
        declared in COUNTER_REGISTRY."""
        assert "latency" in COUNTER_REGISTRY
        for key in new_latency_counters():
            assert key in COUNTER_REGISTRY, key
        clock = FakeClock()
        t = small_table()
        _svc, _pipe, fe = make_frontend(max_batch=2, deadline_s=5.0,
                                        clock=clock)
        f = fe.submit(window_query(t, 0))
        fe.submit(window_query(t, 100))
        for key in f.result().report.counters["latency"]:
            assert key in COUNTER_REGISTRY, key

    def test_frontend_dispatch_registered_as_launch_site(self):
        """CL001 satellite: the dispatch path is in the reviewed
        launch-site registry."""
        assert "ServingFrontend._execute" in LADDER_LAUNCH_SITES


class TestStagingOverlap:
    def test_prestage_then_launch_stages_nothing_new(self):
        t = small_table("fe_cold", seed=7)
        svc = PruningService(mode="ref", verdict_cache=False)
        qs = [window_query(t, 100 * i) for i in range(4)]
        staged = svc.prestage(qs)
        snap = svc.cache.staging_snapshot()
        assert staged == 1                   # one distinct table
        assert snap["prefetch_stages"] == 1
        assert snap["staged_bytes"] > 0
        pipe = PruningPipeline(filter_mode="device", service=svc)
        svc.run_batch(qs, pipe)
        after = svc.cache.staging_snapshot()
        assert after["staged_bytes"] == snap["staged_bytes"]
        # idempotent: a resident plane is not a prefetch
        assert svc.prestage(qs) == 0
        assert svc.cache.staging_snapshot()["prefetch_stages"] == 1

    def test_inline_prefetch_marks_submissions_staged(self):
        clock = FakeClock()
        t = small_table("fe_cold2", seed=9)
        svc, _pipe, fe = make_frontend(max_batch=2, deadline_s=5.0,
                                       clock=clock)
        f = fe.submit(window_query(t, 0))
        fe.submit(window_query(t, 100))
        assert f.result().timestamps["staged"] is not None
        assert svc.cache.staging_snapshot()["prefetch_stages"] == 1
        assert svc.latency["prefetches"] == 2

    def test_prefetch_never_raises(self):
        svc = PruningService(mode="ref", verdict_cache=False)
        assert svc.cache.prefetch(object()) is False


class TestThreaded:
    """Real-clock mode: the batcher/worker threads own timing.  Kept to
    generous deadlines so the suite stays fast and unflaky."""

    def test_deadline_dispatches_partial_batch(self):
        t = small_table()
        svc, _pipe, fe = make_frontend(max_batch=64, deadline_s=0.02,
                                       threaded=True)
        with fe:
            futs = [fe.submit(window_query(t, 100 * i)) for i in range(3)]
            resps = [f.result(timeout=30) for f in futs]
        assert [r.cause for r in resps] == ["deadline"] * 3
        assert svc.latency["deadline_fired"] == 1

    def test_size_cap_and_drain(self):
        t = small_table()
        svc, _pipe, fe = make_frontend(max_batch=2, deadline_s=30.0,
                                       threaded=True)
        with fe:
            futs = [fe.submit(window_query(t, 70 * i)) for i in range(5)]
            fe.drain()                       # flushes the odd one out
            assert all(f.done() for f in futs)
        causes = [f.result().cause for f in futs]
        assert causes.count("size") == 4 and causes.count("flush") == 1
        assert svc.latency["requests"] == 5

    def test_concurrent_submitters_all_resolve(self):
        t = small_table()
        _svc, _pipe, fe = make_frontend(max_batch=4, deadline_s=0.02,
                                        threaded=True)
        results, errs = [], []

        def client(base):
            try:
                fs = [fe.submit(window_query(t, base + 50 * i))
                      for i in range(6)]
                results.extend(f.result(timeout=30) for f in fs)
            except Exception as exc:  # pragma: no cover - failure detail
                errs.append(exc)

        with fe:
            threads = [threading.Thread(target=client, args=(800 * k,))
                       for k in range(3)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            fe.drain()
        assert not errs and len(results) == 18
        rids = [r.rid for r in results]
        assert len(set(rids)) == 18          # one response per submission
