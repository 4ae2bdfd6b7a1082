"""The degradation ladder, rung by rung: every rung of every technique's
chain answers, and answers right.

One case per (technique, answering rung, table geometry, mesh).  A
``FaultInjector`` fails every ``launch.<tech>:<rung>`` site above the rung
under test, so the ladder demotes exactly down to it; the case then pins
the three clauses of the degradation contract (ROADMAP, "The degradation
contract"):

  * **observable** — the rung that answered is the one named: the ladder
    recorded a demotion into it (and into every failed rung between the
    top and it), none below it, and the technique's launch / fallback
    counters say whether a device kernel or the host answered;
  * **never wrong** — reports equal the all-healthy service's and the
    host pipeline's (``assert_reports_equal``); the ``passthrough`` bottom
    keeps a superset of the host's partitions and never certifies FULL;
  * **never raise** — no query is isolated as an error.

Chains (``PruningService._device_rungs`` plus each stage's terminal
rungs): the filter runs verdict -> [sharded_tree -> tree] -> [sharded] ->
device -> host_kernel -> host_oracle -> passthrough; the distinct join,
Bloom join and top-k run [sharded_tree -> tree] -> [sharded] -> device ->
host_oracle.  Tree rungs exist when ``P >= tree_fanout * TREE_MIN_GROUPS``
(``tree`` geometry; ``flat`` sits below it), sharded rungs when the
service has a plane mesh (``shard_planes`` on).  The mesh is the host's
``make_plane_mesh()``: as many devices as ``REPRO_CPU_DEVICES`` forces,
else one device, on which the sharded rungs still exist and run the
unsharded body.  That gives 8/6/6/5 filter cases and 5/3/3/2 for each of
the other three techniques across (tree, mesh), (tree, none),
(flat, mesh), (flat, none): 64 cases.

The ``verdict`` case fails nothing and runs the same batch twice: every
predicate appears twice in the batch, so the doorkeeper admits each on
its first batch, and the second batch is served from the verdict plane
without a launch.
"""

import functools

import numpy as np
import pytest

from repro.core import expr as E
from repro.core.device_stats import TREE_MIN_GROUPS
from repro.core.flow import JoinSpec, PruningPipeline, Query, TableScanSpec
from repro.data.table import Table
from repro.kernels import ops as kops
from repro.serve.prune_service import PruningService
from repro.serve.resilience import RUNGS, FaultInjector

from test_fleet_parity import assert_reports_equal
from test_resilience import NO_SLEEP, assert_scan_superset

FANOUT = 16
NDV_LIMIT = 8                  # build sides: 2-6 keys distinct, > 8 Bloom
GEOMETRIES = {"tree": FANOUT * TREE_MIN_GROUPS + 32, "flat": 40}
MESHES = ("mesh", "none")
TECHNIQUES = ("filter", "join", "join_bloom", "topk")
# ladder runs per batch: one per (table) group, and top-k runs one per
# (table, order column, direction) — its batch orders both ways
GROUPS = {"filter": 1, "join": 1, "join_bloom": 1, "topk": 2}


def chain(tech: str, geometry: str, mesh: str) -> tuple:
    """The technique's rung chain, top first, for this service shape."""
    rungs = []
    if tech == "filter":
        rungs.append("verdict")
    if geometry == "tree":
        rungs += ["sharded_tree"] if mesh == "mesh" else []
        rungs.append("tree")
    rungs += ["sharded"] if mesh == "mesh" else []
    rungs.append("device")
    if tech == "filter":
        rungs += ["host_kernel", "host_oracle", "passthrough"]
    else:
        rungs.append("host_oracle")
    return tuple(rungs)


CASES = [(tech, rung, geometry, mesh)
         for geometry in GEOMETRIES for mesh in MESHES
         for tech in TECHNIQUES
         for rung in chain(tech, geometry, mesh)]


@functools.lru_cache(maxsize=None)
def tables(geometry: str):
    """Probe/fact table ``t`` (P partitions of 4 rows) and a dimension
    ``dim``.  ``t.k`` is clustered with a width-3 range per partition:
    inside int32 and narrow, so the Bloom join's enumeration plane is
    device-eligible and its narrow-range enumeration prunes.  ``t.g``
    has no nulls, so top-k scans on it hold FULL partitions for the
    block-top-k boundary to start from."""
    P = GEOMETRIES[geometry]
    rng = np.random.default_rng(7 + P)
    n = 4 * P
    rows = np.arange(n)
    t = Table.build(f"t_{geometry}", {
        "ts": rows * 10 + rng.integers(0, 10, n),
        "k": (rows // 4) * 3 + rng.integers(0, 3, n),
        "v": rng.integers(-200, 1000, n),
        "g": rng.integers(0, 50, n),
    }, rows_per_partition=4, nulls={"v": rng.random(n) < 0.08})
    m = 60
    dim = Table.build(f"dim_{geometry}", {
        "a": np.arange(m) % 100,
        "k": rng.integers(0, 3 * P // 2, m),
    }, rows_per_partition=8)
    return t, dim


def batch(tech: str, geometry: str) -> list:
    t, dim = tables(geometry)
    P = GEOMETRIES[geometry]
    span = 40 * P
    if tech == "filter":
        # selective ts windows (so the tree rung's group pre-pass runs,
        # not its dense fallback) with FULL, PARTIAL and NO_MATCH verdicts
        preds = [(E.col("ts") >= span // 10) & (E.col("ts") <= span // 5),
                 (E.col("ts") >= span * 4 // 5) & (E.col("v") <= 400),
                 (E.col("ts") >= span // 2) & (E.col("ts") < span * 11 // 20)
                 & (E.col("g") >= 10)]
        return [Query(scans={t.name: TableScanSpec(t, p)})
                for p in preds + preds]
    if tech == "join":
        return [Query(scans={t.name: TableScanSpec(t),
                             dim.name: TableScanSpec(
                                 dim, (E.col("a") >= lo)
                                 & (E.col("a") <= lo + 3))},
                      join=JoinSpec(dim.name, t.name, "k", "k"))
                for lo in (4, 30)]
    if tech == "join_bloom":
        return [Query(scans={t.name: TableScanSpec(t, pred),
                             dim.name: TableScanSpec(dim)},
                      join=JoinSpec(dim.name, t.name, "k", "k"))
                for pred in (E.TruePred(), E.col("v") >= 0)]
    return [Query(scans={t.name: TableScanSpec(t, E.col("g") >= lo)},
                  limit=k, order_by=(t.name, "v", desc))
            for lo, k, desc in ((5, 3, True), (2, 7, False), (8, 5, True))]


def service(geometry: str, mesh: str, inj=None) -> tuple:
    svc = PruningService(mode="ref", fault_injector=inj, sleep=NO_SLEEP,
                         shard_mesh=True if mesh == "mesh" else None,
                         tree_fanout=FANOUT)
    pipe = PruningPipeline(filter_mode="device", service=svc,
                           join_ndv_limit=NDV_LIMIT)
    return svc, pipe


@functools.lru_cache(maxsize=None)
def healthy(tech: str, geometry: str, mesh: str) -> list:
    svc, pipe = service(geometry, mesh)
    return svc.run_batch(batch(tech, geometry), pipe)


@functools.lru_cache(maxsize=None)
def host(tech: str, geometry: str) -> list:
    pipe = PruningPipeline(join_ndv_limit=NDV_LIMIT)
    return [pipe.run(q) for q in batch(tech, geometry)]


def test_batches_reach_the_stage_under_test():
    """The matrix is only as good as its batches: each technique's batch
    launches that technique on the healthy service, and the join
    batches build the summary kind their technique names."""
    for geometry in GEOMETRIES:
        for tech in TECHNIQUES:
            rep = healthy(tech, geometry, "none")[0]
            assert rep.counters["technique"][tech]["launches"] >= 1, \
                (tech, geometry)
            assert rep.counters["technique"][tech]["fallbacks"] == 0
            if tech.startswith("join"):
                kinds = {r.per_scan[tables(geometry)[0].name]["join"]
                         .detail["summary_kind"]
                         for r in healthy(tech, geometry, "none")}
                assert kinds == {"bloom" if tech == "join_bloom"
                                 else "distinct"}
    assert len(CASES) == 64


@pytest.mark.parametrize("tech,rung,geometry,mesh", CASES,
                         ids=["-".join(c) for c in CASES])
def test_rung_answers_and_is_never_wrong(tech, rung, geometry, mesh):
    rungs = chain(tech, geometry, mesh)
    top = rungs.index(rung)
    inj = FaultInjector()
    for above in rungs[:top]:
        if above != "verdict":          # the verdict rung has no site
            inj.add(f"launch.{tech}:{above}")
    svc, pipe = service(geometry, mesh, inj)
    qs = batch(tech, geometry)
    got = svc.run_batch(qs, pipe)
    if rung == "verdict":
        got = svc.run_batch(qs, pipe)
    res = got[0].counters["resilience"]
    counts = {"launches": 0, "fallbacks": 0,
              **got[0].counters["technique"].get(tech, {})}

    # observable: one demotion (per group) into each rung below the
    # chain's entry, down to the answering rung and never past it.  The
    # filter's misses enter its kernel chain from the verdict rung, which
    # runs that chain itself: a verdict miss is not a demotion.
    groups = GROUPS[tech]
    first = 1 if tech == "filter" else 0
    want = {r: 0 for r in RUNGS[1:]}
    for r in rungs[first + 1:top + 1]:
        want[r] = groups
    assert res["demotions"] == want
    assert res["passthroughs"] == (groups if rung == "passthrough" else 0)
    if rung == "verdict":
        assert res["verdict_hits"] == 3 and res["verdict_misses"] == 0
        assert res["verdict_deduped"] == 3
        assert counts["launches"] == 0 and counts["fallbacks"] == 0
    elif rung in ("host_kernel", "host_oracle", "passthrough"):
        assert counts["launches"] == 0
        # the join matchers count one fallback per summary, the filter
        # and top-k one per ladder run
        assert counts["fallbacks"] == (0 if rung == "passthrough" else
                                       len(qs) if tech.startswith("join")
                                       else groups)
    else:
        assert counts["launches"] == groups and counts["fallbacks"] == 0
        if (rung in ("sharded", "device") and mesh == "mesh"
                and svc.shard_mesh.devices.size > 1):
            # the technique's launch is the batch's last: it split the
            # plane over the mesh on the sharded rung, and only there
            assert (kops.last_launch_shards() > 1) == (rung == "sharded")

    # never raise
    assert res["errors"] == 0 and res["salvaged_batches"] == 0

    # never wrong
    label = f"{tech}@{rung}"
    if rung == "passthrough":
        assert_scan_superset(qs, got, host(tech, geometry), label)
        for rep, q in zip(got, qs):
            for name in q.scans:
                assert set(np.unique(rep.scan_sets[name].match)) <= {1}, \
                    f"{label}: passthrough certified FULL"
    else:
        assert_reports_equal(qs, got, healthy(tech, geometry, mesh),
                             f"{label} vs healthy")
        assert_reports_equal(qs, got, host(tech, geometry),
                             f"{label} vs host")
