"""The program's host spans (``repro.spans``).

Every stage of the served path opens a ``prune.*`` span on the profiler's
clock: the stages of ``run_batch`` nest inside ``prune.run_batch``, each
ladder attempt is a ``prune.rung.<name>``, and every batched kernel
wrapper opens ``pack``, ``dispatch``, ``device_wait``, ``d2h`` and
``unpack`` once per launch.  Traces are captured on the CPU with the
benchmark's own ``bench.tracing.Capture``.
"""

import collections
import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import tracing  # noqa: E402
from repro import spans  # noqa: E402
from repro.core import expr as E  # noqa: E402
from repro.core.device_stats import DeviceStatsCache, tree_entry_for  # noqa: E402
from repro.core.flow import JoinSpec, PruningPipeline, Query, TableScanSpec  # noqa: E402
from repro.core.prune_filter import extract_ranges  # noqa: E402
from repro.core.prune_join import BlockedBloom  # noqa: E402
from repro.data.generator import make_events_table, make_users_table  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve.frontend import ServingFrontend  # noqa: E402
from repro.serve.prune_service import PruningService  # noqa: E402
from repro.serve.resilience import RUNGS, FaultInjector  # noqa: E402
from repro.spans import PREFIX, SPANS  # noqa: E402

TS_MAX = 10_000_000
WRAPPER_SPANS = ("pack", "dispatch", "device_wait", "d2h", "unpack")


def tables(seed: int = 5):
    """``events`` in 256 partitions (below the tree rungs' floor of
    1,024) and a 40-partition ``users`` dimension."""
    rng = np.random.default_rng(seed)
    events = make_events_table(rng, n_rows=256 * 8, rows_per_partition=8,
                               ts_clustering=0.999, user_clustering=0.999)
    users = make_users_table(rng, n_rows=4000, rows_per_partition=100)
    return events, users


def _window(lo: float, hi: float):
    return ((E.col("ts") >= int(TS_MAX * lo))
            & (E.col("ts") <= int(TS_MAX * hi)))


def mixed_batch(events, users):
    """Filter, LIMIT, a distinct and a Bloom join, top-k, and an ``IN``
    predicate that does not lower to ranges (host fallback)."""
    def join(age_lo, age_hi):
        return Query(scans={
            "events": TableScanSpec(events, _window(0.0, 0.6)),
            "users": TableScanSpec(users, (E.col("age") >= age_lo)
                                   & (E.col("age") <= age_hi))},
            join=JoinSpec("users", "events", "id", "user_id"))

    return [
        Query(scans={"events": TableScanSpec(events, _window(0.2, 0.3))}),
        Query(scans={"events": TableScanSpec(events, _window(0.1, 0.9))},
              limit=10),
        join(70, 72),        # ~170 build keys: a distinct summary
        join(20, 60),        # ~2,300 build keys: a Bloom summary
        Query(scans={"events": TableScanSpec(events, _window(0.3, 0.6))},
              limit=5, order_by=("events", "num_sightings", True)),
        Query(scans={"events": TableScanSpec(
            events, E.in_(E.col("user_id"), [7, 8, 9]))}),
    ]


def traced(fn):
    """(fn's result, the ``prune.*`` host events it recorded)."""
    cap = tracing.Capture()
    cap.start()
    try:
        out = fn()
    finally:
        tr = cap.stop()
    return out, [ev for ev in tr.host if ev[0].startswith(PREFIX)]


def names(events) -> collections.Counter:
    return collections.Counter(n[len(PREFIX):] for n, _, _ in events)


@pytest.fixture(scope="module")
def served():
    events, users = tables()
    svc = PruningService(mode="ref")
    pipe = PruningPipeline(filter_mode="device", service=svc,
                           join_ndv_limit=256)
    batch = mixed_batch(events, users)
    svc.run_batch(batch, pipe)             # stage the planes, compile
    reports, evs = traced(lambda: svc.run_batch(batch, pipe))
    kinds = {r.per_scan["events"]["join"].detail["summary_kind"]
             for r in reports if "join" in r.per_scan["events"]}
    return evs, kinds


def test_every_stage_of_a_mixed_batch_is_a_span_inside_run_batch(served):
    evs, kinds = served
    assert kinds == {"distinct", "bloom"}
    got = names(evs)
    assert got["run_batch"] == 1
    expected = {"validate", "technique.filter", "technique.limit",
                "technique.join", "technique.topk", "finish", "account",
                "filter.plan", "filter.verdict", "filter.assemble",
                "filter.host_eval", "join.build_keys", "join.summarize",
                "join.apply", "topk.init", "rung.verdict", "rung.device",
                *WRAPPER_SPANS}
    assert expected <= set(got), expected - set(got)
    (_, s0, e0), = [ev for ev in evs if ev[0] == PREFIX + "run_batch"]
    assert all(s0 <= s and e <= e0 for _, s, e in evs)
    # one build-key pass, summary and probe per join query
    assert got["join.build_keys"] == got["join.summarize"] == 2
    assert got["join.apply"] == 2


@pytest.mark.parametrize("windows", [[(0.2, 0.3)], [(0.0, 0.1), (0.9, 1.0)]],
                         ids=["one_run", "two_runs"])
def test_the_build_scan_span_says_what_it_gathered(monkeypatch, windows):
    """``events`` (no null masks) as the build side: a window of the
    clustered ``ts`` is one run of partitions, read as a view; two
    windows are two runs, copied as ``rows x 8`` bytes per column."""
    events, users = tables()
    build = E.Or(tuple(_window(lo, hi) for lo, hi in windows))
    q = Query(scans={"events": TableScanSpec(events, build),
                     "users": TableScanSpec(users, E.col("age") >= 30)},
              join=JoinSpec("events", "users", "user_id", "id"))
    seen = []                  # each span's name and keyword metadata
    monkeypatch.setattr(spans, "TraceAnnotation", lambda name, **meta: (
        seen.append((name, meta)) or contextlib.nullcontext()))
    report = PruningPipeline(filter_mode="host").run(q)
    (meta,) = [m for n, m in seen if n == PREFIX + "join.build_keys"]
    ids = report.scan_sets["events"].part_ids
    rows = int(np.diff(events.part_bounds)[ids].sum())
    assert meta["P"] == len(ids)
    assert meta["cols"] == 2                       # ts and user_id
    assert meta["runs"] == len(windows)
    assert meta["rows"] == rows
    assert meta["bytes"] == (0 if len(windows) == 1
                             else rows * 8 * meta["cols"])
    assert all(isinstance(v, int) for v in meta.values())


def test_every_recorded_name_is_declared(served):
    evs, _ = served
    assert set(names(evs)) <= set(SPANS)
    assert {"rung." + r for r in RUNGS} <= set(SPANS)
    assert len(set(SPANS)) == len(SPANS)


def test_a_failing_device_launch_shows_as_the_host_kernel_rung():
    events, users = tables()
    faults = FaultInjector(seed=0).add("launch.filter:device")
    svc = PruningService(mode="ref", fault_injector=faults,
                         verdict_cache=False, sleep=lambda _s: None)
    batch = mixed_batch(events, users)[:2]
    reports, evs = traced(lambda: svc.run_batch(batch))
    got = names(evs)
    assert got["rung.device"] == 2          # the attempt and its retry
    assert got["backoff"] == 1
    assert got["rung.host_kernel"] == 1
    assert set(got) <= set(SPANS)
    assert reports[0].counters["resilience"]["demotions"]["host_kernel"] == 1


def test_the_front_end_spans_its_dispatch():
    events, users = tables()
    fe = ServingFrontend(PruningService(mode="ref"), max_batch=2,
                         threaded=False)
    batch = mixed_batch(events, users)[:2]

    def serve():            # the second submit fills the batch: dispatch
        return [f.result() for f in [fe.submit(q) for q in batch]]

    _, evs = traced(serve)
    got = names(evs)
    assert got["frontend.execute"] == got["frontend.prestage"] == 1
    assert got["run_batch"] == 1
    (_, s0, e0), = [ev for ev in evs if ev[0] == PREFIX + "frontend.execute"]
    assert all(s0 <= s and e <= e0 for _, s, e in evs)


# -- the batched kernel wrappers ------------------------------------------

def wrapper_span_counts(mode: str, mesh=None) -> dict:
    """Per batched wrapper, the ``prune.*`` spans one call records."""
    events, users = tables()
    cache = DeviceStatsCache()
    dstats = cache.get(events)
    ranges = [extract_ranges(_window(a, a + 0.02), events.stats)
              for a in (0.1, 0.4, 0.7)]
    dist = [np.unique(users.data["id"][:n]).astype(np.float64)
            for n in (5, 50, 500)]
    blooms = []
    for keys in dist:
        b = BlockedBloom(len(keys))
        b.add(keys.astype(np.int64))
        blooms.append(b)
    pmin, pmax = cache.join_key_plane(events, "user_id")
    epmin, width, wmax, _ok = cache.enum_plane(events, "user_id")
    plane = cache.block_topk_plane(events, "num_sightings", True)
    mask = np.zeros((3, events.num_partitions), dtype=np.float32)
    mask[:, ::7] = 1.0
    calls = {
        "filter": lambda: ops.prune_ranges_batched_device(
            ranges, dstats, mode, mesh=mesh),
        "join": lambda: ops.join_overlap_batched_device(
            dist, pmin, pmax, mode, mesh=mesh),
        "bloom": lambda: ops.bloom_probe_batched_device(
            blooms, epmin, width, wmax, 64, mode, mesh=mesh),
        "topk": lambda: ops.topk_init_batched_device(
            plane, mask, 8, mode, mesh=mesh),
    }
    if mesh is None:
        te = tree_entry_for(dstats, fanout=16)
        calls["filter_tree"] = lambda: ops.prune_ranges_batched_tree(
            ranges, dstats, te, mode)
    out = {}
    for name, call in calls.items():
        call()                             # compile outside the trace
        _, evs = traced(call)
        out[name] = dict(names(evs))
        out[name]["shards"] = ops.last_launch_shards()
        out[name]["path"] = ops.last_tree_stats().get("path")
    return out


@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_each_batched_wrapper_spans_its_launch_once(mode):
    counts = wrapper_span_counts(mode)
    # the jnp oracle of the flat filter and of the tree rung is a device
    # launch in every mode; the join, Bloom and top-k oracles are numpy
    launching = (("filter", "join", "bloom", "topk") if mode == "interpret"
                 else ("filter",))
    for name in launching:
        assert {k: counts[name].get(k) for k in WRAPPER_SPANS} == \
            dict.fromkeys(WRAPPER_SPANS, 1), name
    for name in set(("join", "bloom", "topk")) - set(launching):
        assert not set(WRAPPER_SPANS) & set(counts[name]), name
    # the hierarchical filter is two launches: group pre-pass, then leaves
    assert counts["filter_tree"]["path"] == "tree"
    assert {k: counts["filter_tree"].get(k) for k in WRAPPER_SPANS} == \
        dict.fromkeys(WRAPPER_SPANS, 2)


def test_sharded_wrappers_span_their_launch_once():
    """The partition-sharded launches, on two forced CPU devices."""
    code = (
        "import json, sys\n"
        f"sys.path[:0] = [{os.path.join(ROOT, 'tests')!r}, {ROOT!r}]\n"
        "from repro.kernels.ops import make_plane_mesh\n"
        "import test_spans\n"
        "print(json.dumps(test_spans.wrapper_span_counts("
        "'ref', mesh=make_plane_mesh())))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"), ROOT]))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    counts = json.loads(r.stdout.strip().splitlines()[-1])
    for name in ("filter", "join", "bloom", "topk"):
        assert counts[name]["shards"] == 2, name
        assert {k: counts[name].get(k) for k in WRAPPER_SPANS} == \
            dict.fromkeys(WRAPPER_SPANS, 1), name
