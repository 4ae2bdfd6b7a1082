"""Per-batch host stages and device idle time by stage, read from the
program's ``prune.*`` spans: synthetic traces with known answers, the
three readers on and off the chip, and one trace the program records."""

import pytest

from bench import harness, program, stages, tracing
from bench.spec import Benchmark, metric_reader
from bench.tests.common import ROOT, SEED, SPEC, small_tables
from bench.trafficgen import Traffic

pytestmark = pytest.mark.filterwarnings("ignore")

PEAKS = dict(hbm_bytes_per_s=819e9)


def us(a, b):
    return a * 1e3, b * 1e3          # microseconds -> trace nanoseconds


def _trace():
    """Two batches in a 1,000 us window, device busy at [100, 110) and
    [600, 610); a third batch after the window is never counted."""
    ops = {"/device:TPU:0": [("minmax_prune_batched.1", *us(100, 110)),
                             ("fusion.1", *us(600, 610))]}
    host = [("bench.window", *us(0, 1000)),
            ("bench.run_batch", *us(50, 450)),
            ("prune.run_batch", *us(50, 450)),
            ("prune.validate", *us(50, 60)),
            ("prune.technique.filter", *us(60, 440)),
            ("prune.pack", *us(60, 70)),
            ("prune.dispatch", *us(70, 80)),
            ("prune.device_wait", *us(80, 120)),
            ("prune.d2h", *us(120, 300)),
            ("prune.unpack", *us(300, 340)),
            ("prune.filter.assemble", *us(340, 420)),
            ("prune.run_batch", *us(500, 950)),
            ("prune.d2h", *us(610, 650)),
            ("prune.unpack", *us(650, 660)),
            ("prune.filter.assemble", *us(660, 700)),
            ("prune.join.build_keys", *us(700, 800)),
            ("prune.join.summarize", *us(800, 850)),
            ("prune.rung.host_oracle", *us(850, 900)),
            ("prune.d2h", *us(960, 990)),           # outside any batch
            ("prune.run_batch", *us(1100, 1200)),   # after the window
            ("prune.d2h", *us(1110, 1190))]
    return tracing.Trace(ops, host, us(0, 1000))


def test_per_batch_ms_is_the_median_of_each_batch_sum():
    tr = _trace()
    assert stages.per_batch_ms(tr, ("d2h",)) == pytest.approx(0.110)
    assert stages.per_batch_ms(tr, ("unpack", "filter.assemble")) == \
        pytest.approx(0.085)
    assert stages.per_batch_ms(tr, ("join.build_keys", "join.summarize",
                                    "rung.host_oracle")) == pytest.approx(0.1)
    assert stages.per_batch_ms(tr, ("no.such.stage",)) == 0.0
    no_batch = tracing.Trace(tr.ops, [ev for ev in tr.host
                                      if ev[0] != "prune.run_batch"],
                             tr.window)
    assert stages.per_batch_ms(no_batch, ("d2h",)) is None


def test_idle_by_stage_names_the_innermost_covering_span():
    tr = _trace()
    got = stages.idle_by_stage(tr)
    want_us = {"no program span": 120, "prune.validate": 10,
               "prune.pack": 10, "prune.dispatch": 10,
               "prune.device_wait": 30, "prune.d2h": 250,
               "prune.unpack": 50, "prune.filter.assemble": 120,
               "prune.technique.filter": 20, "prune.run_batch": 160,
               "prune.join.build_keys": 100, "prune.join.summarize": 50,
               "prune.rung.host_oracle": 50}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want_us.items()})
    assert sum(got.values()) == pytest.approx(
        tr.window_s - tracing.busy_s(tr))
    # the longest gap alone: [110, 600) us
    top = stages.idle_by_stage(tr, top=1)
    assert sum(top.values()) == pytest.approx(490e-6)
    assert top["prune.run_batch"] == pytest.approx(110e-6)
    assert top["no program span"] == pytest.approx(50e-6)


def test_a_trace_without_program_spans_reads_nothing():
    tr = _trace()
    bare = tracing.Trace(tr.ops, [ev for ev in tr.host
                                  if not ev[0].startswith("prune.")],
                         tr.window)
    assert stages.idle_by_stage(bare) == pytest.approx(
        {"no program span": 980e-6})
    for name in ("d2h_ms", "assemble_ms", "host_prune_ms"):
        assert metric_reader(name)(harness.RunData(
            1.0, [], [], bare, PEAKS)) is None


@pytest.mark.parametrize("name,want", [("d2h_ms", 0.110),
                                       ("assemble_ms", 0.085),
                                       ("host_prune_ms", 0.100)])
def test_span_readers_on_and_off_the_chip(name, want):
    read = metric_reader(name)
    assert read(harness.RunData(1.0, [], [], _trace(), PEAKS)) == \
        pytest.approx(want)
    # no chip (no peaks): a CPU time never stands under these names
    assert read(harness.RunData(1.0, [], [], _trace(), None)) is None
    assert read(harness.RunData(1.0, [], [], None, PEAKS)) is None
    entry, = [m for m in SPEC["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_span" and entry["unit"] == "ms"


def test_a_recorded_batch_is_read_stage_by_stage():
    """A trace the program itself records: every stage of the batch is a
    span inside ``prune.run_batch`` and the stage reductions read it."""
    from repro.serve.prune_service import PruningService
    bench = Benchmark(ROOT)
    data = small_tables("events-1m")
    tables = {n: program.build_table(td) for n, td in data.items()}
    traffic = Traffic(bench.traffic("dense-batch"), SEED)
    queries = [program.to_query(q, tables) for q in traffic.queries(8, 0)]
    svc = PruningService(mode="auto")
    svc.run_batch(queries)                  # stage and compile
    cap = tracing.Capture()
    cap.start()
    svc.run_batch(queries)
    tr = cap.stop()
    batch, = [(s, e) for n, s, e in tr.host if n == "prune.run_batch"]
    tr.window = batch
    assert stages.per_batch_ms(tr, ("d2h",)) > 0
    assert stages.per_batch_ms(tr, ("unpack", "filter.assemble")) > 0
    # no device plane on the CPU: the whole batch is one idle gap, and
    # the program's spans name all of it
    by = stages.idle_by_stage(tr)
    assert by.get("no program span", 0.0) == 0.0
    assert sum(by.values()) == pytest.approx(tr.window_s)
