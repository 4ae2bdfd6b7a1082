"""Trace-to-metric reductions, the end-to-end statistics, and the filter
roofline's byte count, on inputs whose answers are known."""

import time

import pytest

from bench import harness, tracing
from bench.spec import metric_reader

pytestmark = pytest.mark.filterwarnings("ignore")


def _trace():
    ops = {"/device:TPU:0": [("minmax_prune_batched.1", 0.0, 10.0),
                             ("fusion.3", 5.0, 20.0),
                             ("copy.2", 30.0, 40.0)]}
    host = [("bench.window", 0.0, 50.0), ("bench.run_batch", 18.0, 32.0),
            ("bench.generator", 0.0, 50.0),
            ("np.asarray(jax.Array)", 12.0, 15.0),
            ("np.asarray(jax.Array)", 60.0, 70.0)]      # after the window
    return tracing.Trace(ops, host, (0.0, 50.0))


def test_busy_union_idle_share_and_kernel_time():
    tr = _trace()
    assert tracing.union_length([(0, 10), (5, 20), (30, 40)]) == 30
    assert tracing.busy_s(tr) == pytest.approx(30e-9)
    assert tracing.idle_share(tr) == pytest.approx(0.4)
    assert tracing.kernel_s(tr, ("minmax_prune_batched",)) == pytest.approx(10e-9)
    assert tracing.host_s(tr, "np.asarray(jax.Array)") == pytest.approx(3e-9)
    assert [n for n, _, _ in tr.spans] == ["bench.window", "bench.run_batch",
                                           "bench.generator"]
    b = tracing.breakdown(tr)
    assert b["device_ops"][0] == ["fusion.3", pytest.approx(15e-9)]
    # gaps [20, 30) and [40, 50): the shorter covering span names each
    assert b["idle_gaps"] == [["bench.run_batch", pytest.approx(10e-9)],
                              ["bench.generator", pytest.approx(10e-9)]]


def test_a_recorded_trace_is_read_back(tmp_path):
    import jax
    import jax.numpy as jnp
    cap = tracing.Capture()
    cap.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.run_batch"):
            jax.jit(lambda a: a @ a)(jnp.ones((64, 64))).block_until_ready()
            time.sleep(0.01)
    tr = cap.stop()
    assert tr.window_s >= 0.01
    assert any(n == "bench.run_batch" for n, _, _ in tr.spans)
    with jax.profiler.trace(str(tmp_path)):
        jax.jit(lambda a: a + 1)(jnp.ones(8)).block_until_ready()
    assert any("/host:CPU" in row
               for row in tracing.describe(tracing.xplanes(str(tmp_path))[0]))


def test_latency_from_due_times_and_qps_over_the_window():
    due = [10.0 + 0.1 * i for i in range(20)]
    reqs = [dict(due=d, done=d + 0.001 * (i + 1), latency_ms=1.0 * (i + 1))
            for i, d in enumerate(due)]
    out = dict(completed=20, window_s=4.0, requests=reqs, t_close=14.0)
    m = harness.end_to_end(out, setup_s=7.5)
    assert m["qps"] == 5.0 and m["setup_s"] == 7.5
    assert m["p50_ms"] == pytest.approx(10.5)
    assert m["p95_ms"] == pytest.approx(19.05)
    # a request that never finished counts as late as the wait allowed
    reqs[0] = dict(due=10.0, done=None, latency_ms=None)
    m = harness.end_to_end(out, setup_s=7.5)
    assert max(m["p95_ms"], m["p50_ms"]) < (14.0 + harness.LATE_WAIT_S - 10.0) * 1e3
    assert harness.percentile([r["latency_ms"] or 64e3 for r in reqs], 100) == 64e3


@pytest.mark.parametrize("name", ["idle_share.batch", "idle_share.open"])
def test_idle_share_readers_give_the_idle_percent(name):
    read = metric_reader(name)
    run = harness.RunData(1.0, [], [], _trace(), None)
    assert read(run) == pytest.approx(40.0)
    run.trace = tracing.Trace({}, [], (0.0, 50.0))     # no device plane
    assert read(run) is None
    run.trace = None
    assert read(run) is None


def test_sweep_sustains_a_rate_only_without_backlog_or_loss():
    from bench import sweep
    flat = [100.0] * 40
    growing = [100.0 + 10 * i for i in range(40)]
    assert sweep.backlog_growth(flat) == 1.0
    assert sweep.sustained(0, sweep.backlog_growth(flat))
    assert not sweep.sustained(0, sweep.backlog_growth(growing))
    assert not sweep.sustained(1, sweep.backlog_growth(flat))


def test_queue_and_batch_ms_are_medians_of_the_records():
    reqs = [dict(queue_ms=v) for v in (5.0, 1.0, 3.0)] + [dict(queue_ms=None)]
    batches = [dict(t_launch=0.0, t_done=0.25), dict(t_launch=1.0, t_done=1.5),
               dict(t_launch=2.0, t_done=2.125)]
    run = harness.RunData(1.0, reqs, batches, None, None)
    assert metric_reader("queue_ms")(run) == 3.0
    assert metric_reader("batch_ms")(run) == 250.0
    assert metric_reader("queue_ms")(harness.RunData(1.0, [], [], None,
                                                     None)) is None


def test_filter_roofline_counts_least_bytes():
    read = metric_reader("filter_roofline")
    P = 1 << 20
    batch = dict(tables={"events": dict(q=64, cols=3, P=P)},
                 flat_filter_launches=1)
    nbytes = 3 * 3 * 4 * P + 64 * P // 4
    assert nbytes == 54_525_952
    run = harness.RunData(1.0, [], [batch], _trace(),
                          dict(hbm_bytes_per_s=819e9))
    want = 100.0 * nbytes / 819e9 / 10e-9
    assert read(run) == pytest.approx(want)
    batch["flat_filter_launches"] = 0        # tree rung ran: not counted
    assert read(run) is None
