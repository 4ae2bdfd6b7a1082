"""The plane-integrity protocol, family by family.

Every resident plane family (``PLANE_FAMILIES``) must keep the same
protocol (ROADMAP, "The degradation contract"): stamp a checksum at
stage and after every delta replay, verify on the sampled schedule,
force-verify a restage after quarantine or budget eviction, quarantine
a torn plane once and restage it from host truth, and raise
``PlaneIntegrityError`` on a second failure, which the serving ladder
demotes past.  One case per (family, event), six events:

  * ``fresh`` — a fresh stage is stamped and verifies clean under
    ``integrity_sample=1``;
  * ``torn_once`` — one torn stage is quarantined once, and the answer
    is exact;
  * ``torn_always`` — every stage torn raises ``PlaneIntegrityError``
    inside the cache, yet the batch never raises and its answer is exact
    (or a superset, where the id says so);
  * ``evicted`` — a restage after budget eviction is force-verified:
    with sampling off, a tear in that restage is still caught;
  * ``append`` / ``drop`` — a delta replay after ``append_partitions`` /
    ``drop_partitions`` re-stamps the plane in place, and a tear after
    the next replay is caught; the answers stay exact.

Each family is driven by the traffic that reads it: filter queries
(``stat``, ``tree_stat``, and ``verdict`` with every predicate twice so
the doorkeeper admits it on its first batch), a distinct-key join
(``join_key``), a Bloom join (``enum``) and top-k (``block_topk``).  The
table is tree-eligible (``P >= tree_fanout * TREE_MIN_GROUPS``), so the
tree family is staged by the same batches.  These cases are the protocol
a single plane-family abstraction must keep.
"""

import numpy as np
import pytest

from repro.core import expr as E
from repro.core.device_stats import (PLANE_FAMILIES, TREE_MIN_GROUPS,
                                     DeviceStatsCache, PlaneIntegrityError,
                                     plane_checksum)
from repro.core.flow import JoinSpec, PruningPipeline, Query, TableScanSpec
from repro.core.prune_filter import eval_tv
from repro.data.table import Table
from repro.serve.prune_service import PruningService
from repro.serve.resilience import FaultInjector

from test_fleet_parity import assert_reports_equal
from test_resilience import NO_SLEEP, assert_scan_superset

FANOUT = 16
NDV_LIMIT = 8
P = FANOUT * TREE_MIN_GROUPS + 32
STORES = {"stat": "entries", "join_key": "key_planes", "enum": "enum_planes",
          "block_topk": "topk_planes", "tree_stat": "tree_planes",
          "verdict": "verdict_planes"}
EVENTS = ("fresh", "torn_once", "torn_always", "evicted", "append", "drop")

# What a family does differently from the rest, and why.  Each entry
# names the (family, event) case whose id carries it.
NOTES = {
    # join_device_eligible reads the enumeration plane's domain_ok before
    # any ladder runs, so a plane that can never verify fails the batched
    # join outside the ladder: run_batch salvages the batch per query and
    # the Bloom join's probe degrades to a passthrough superset
    ("enum", "torn_always"): "superset_eligibility_outside_ladder",
    # verdict rows are repaired, not restaged, on append: the appended
    # slots are evaluated on the host and patched in place
    ("verdict", "append"): "repaired_in_place",
}
CASES = [(family, event) for family in PLANE_FAMILIES for event in EVENTS]
IDS = ["-".join((f, e) + ((NOTES[f, e],) if (f, e) in NOTES else ()))
       for f, e in CASES]


def _rows(rng, lo_part: int, n_parts: int) -> dict:
    n = 4 * n_parts
    rows = np.arange(4 * lo_part, 4 * lo_part + n)
    return {"ts": rows * 10 + rng.integers(0, 10, n),
            "k": (rows // 4) * 3 + rng.integers(0, 3, n),
            "v": rng.integers(-200, 1000, n),
            "g": rng.integers(0, 50, n)}


def make_tables(seed: int = 3, name: str = "t"):
    """A tree-eligible fact table (narrow clustered int32 join keys, as
    the Bloom join's enumeration plane needs) and a dimension."""
    rng = np.random.default_rng(seed)
    t = Table.build(name, _rows(rng, 0, P), rows_per_partition=4,
                    nulls={"v": rng.random(4 * P) < 0.08})
    dim = Table.build(f"{name}_dim", {
        "a": np.arange(60) % 100,
        "k": rng.integers(0, 3 * P // 2, 60),
    }, rows_per_partition=8)
    return t, dim


def batch(family: str, t, dim, rnd: int = 0) -> list:
    """The family's traffic.  Filter windows move with ``rnd`` except
    for ``verdict``: a repeated predicate is served from the verdict
    plane and would never reach the stat and tree planes again."""
    span = 40 * P
    if family == "join_key":
        return [Query(scans={t.name: TableScanSpec(t),
                             dim.name: TableScanSpec(
                                 dim, (E.col("a") >= 4) & (E.col("a") <= 7))},
                      join=JoinSpec(dim.name, t.name, "k", "k"))]
    if family == "enum":
        return [Query(scans={t.name: TableScanSpec(t, E.col("v") >= 0),
                             dim.name: TableScanSpec(dim)},
                      join=JoinSpec(dim.name, t.name, "k", "k"))]
    if family == "block_topk":
        return [Query(scans={t.name: TableScanSpec(t, E.col("g") >= 5)},
                      limit=4, order_by=(t.name, "v", True))]
    lo = 0 if family == "verdict" else rnd * span // 50
    preds = [(E.col("ts") >= lo + span // 10) & (E.col("ts") <= span // 5),
             (E.col("ts") >= lo + span * 4 // 5) & (E.col("v") <= 400)]
    return [Query(scans={t.name: TableScanSpec(t, p)}) for p in preds + preds]


def stage(cache, family: str, t):
    """Stage (or bring current) ``t``'s plane of ``family`` directly on
    a cache, as the service's getters do."""
    if family == "stat":
        cache.get(t)
    elif family == "join_key":
        cache.join_key_plane(t, "k")
    elif family == "enum":
        cache.enum_plane(t, "k")
    elif family == "block_topk":
        cache.block_topk_plane(t, "v", True)
    elif family == "tree_stat":
        cache.tree_plane(t, cache.get(t))
    else:
        pred = batch("verdict", t, None)[0].scans[t.name].pred
        cache.verdict_record(t, pred, E.canonical_key(pred),
                             eval_tv(pred, t.stats))


def resident(cache, family: str, t) -> list:
    """(arrays, stamp) of ``t``'s resident planes of ``family``."""
    store = getattr(cache, STORES[family])
    out = []
    for key, e in store.items():
        if key[0] != t.name:
            continue
        if family == "stat":
            out.append((e.planes, e.checksum))
        else:
            out.append((e.arrays, e.meta["checksum"]))
    return out


def assert_stamped_clean(cache, family: str, t):
    """``t``'s resident planes of ``family`` are current and match their
    stamps."""
    store = getattr(cache, STORES[family])
    planes = [e for key, e in store.items() if key[0] == t.name]
    assert planes, f"no resident {family} plane"
    for arrays, stamp in resident(cache, family, t):
        assert stamp is not None and plane_checksum(arrays) == stamp
    assert all(e.version == t.version for e in planes), \
        f"{family} plane not brought current"


def service(inj=None, integrity_sample: int = 1):
    svc = PruningService(mode="ref", fault_injector=inj, sleep=NO_SLEEP,
                         integrity_sample=integrity_sample,
                         tree_fanout=FANOUT)
    pipe = PruningPipeline(filter_mode="device", service=svc,
                           join_ndv_limit=NDV_LIMIT)
    return svc, pipe


def run(svc, pipe, family, t, dim, label, rnd=0, superset=False,
        served=True):
    """One batch of the family's traffic (twice for ``verdict``: its
    second batch is ``served`` from the plane), checked against the host
    pipeline; returns the last batch's reports."""
    qs = batch(family, t, dim, rnd)
    host = PruningPipeline(join_ndv_limit=NDV_LIMIT)
    want = [host.run(q) for q in qs]
    for _ in range(2 if family == "verdict" else 1):
        got = svc.run_batch(qs, pipe)
        if superset:
            assert_scan_superset(qs, got, want, label)
        else:
            assert_reports_equal(qs, got, want, label)
    if family == "verdict":
        hits = got[0].counters["resilience"]["verdict_hits"]
        assert hits == (2 if served else 0), label
    return got


def append(t, rng, n_parts: int = 8) -> int:
    t.append_partitions(_rows(rng, t.num_partitions, n_parts),
                        rows_per_partition=4)
    return n_parts


@pytest.mark.parametrize("family,event", CASES, ids=IDS)
def test_plane_family_keeps_the_integrity_protocol(family, event):
    t, dim = make_tables()
    site = f"stage.{family}"

    if event == "fresh":
        svc, pipe = service()
        run(svc, pipe, family, t, dim, event)
        assert_stamped_clean(svc.cache, family, t)
        integ = svc.cache.integrity
        assert integ["verifications"] > 0
        assert integ["checksum_failures"] == 0 and integ["quarantines"] == 0

    elif event == "torn_once":
        inj = FaultInjector(seed=1).add(site, kind="corrupt", times=1)
        svc, pipe = service(inj)
        got = run(svc, pipe, family, t, dim, event)
        assert inj.log == [(site, "corrupt")]
        integ = svc.cache.integrity
        assert integ["checksum_failures"] == 1 and integ["quarantines"] == 1
        assert_stamped_clean(svc.cache, family, t)
        # healed below the ladder: no rung was given up
        assert not any(got[0].counters["resilience"]["demotions"].values())

    elif event == "torn_always":
        inj = FaultInjector(seed=2).add(site, kind="corrupt")
        cache = DeviceStatsCache(fault_injector=inj, integrity_sample=1,
                                 tree_fanout=FANOUT)
        with pytest.raises(PlaneIntegrityError):
            stage(cache, family, t)
        assert not resident(cache, family, t), "a torn plane stayed resident"
        svc, pipe = service(FaultInjector(seed=2).add(site, kind="corrupt"))
        superset = family == "enum"         # see NOTES
        got = run(svc, pipe, family, t, dim, event, superset=superset,
                  served=False)
        res = got[0].counters["resilience"]
        if superset:
            assert res["salvaged_batches"] == 1
            assert isinstance(svc.ladder.last_errors["run_batch"],
                              PlaneIntegrityError)
        else:
            assert res["errors"] == 0 and res["salvaged_batches"] == 0
            assert res["passthroughs"] == 0
            assert any(res["demotions"].values())
        assert svc.cache.integrity["quarantines"] >= 2

    elif event == "evicted":
        # sampling off: only a forced verify can see the tear in the
        # restage (the third stage of this family: t, other, t again)
        inj = FaultInjector(seed=3).add(site, kind="corrupt", after=2,
                                        times=1)
        cache = DeviceStatsCache(fault_injector=inj, integrity_sample=0,
                                 tree_fanout=FANOUT)
        other, _ = make_tables(seed=4, name="other")
        stage(cache, family, t)
        keys = [k for k in getattr(cache, STORES[family]) if k[0] == t.name]
        cache.memory.budget_bytes = cache.resident_bytes
        stage(cache, family, other)
        assert all(cache.memory.was_evicted(family, k) for k in keys)
        assert not resident(cache, family, t)
        cache.memory.budget_bytes = None
        stage(cache, family, t)
        assert inj.log == [(site, "corrupt")]
        assert cache.integrity["checksum_failures"] == 1
        assert cache.integrity["quarantines"] == 1
        assert_stamped_clean(cache, family, t)

    else:                                   # append / drop
        svc, pipe = service()
        rng = np.random.default_rng(5)
        run(svc, pipe, family, t, dim, "staged")

        def dml():
            if event == "append":
                svc.notify_append(t.name, append(t, rng))
            else:
                live = np.where(t.live_mask)[0]
                t.drop_partitions(live[rng.choice(live.size, 3,
                                                  replace=False)])
                svc.notify_drop(t.name)

        # a clean replay re-stamps the resident plane in place
        before = svc.cache.staging_snapshot()
        repairs = svc.cache.integrity["verdict_repairs"]
        dml()
        run(svc, pipe, family, t, dim, f"{event} replay", rnd=1)
        after = svc.cache.staging_snapshot()
        assert after["delta_stages"] > before["delta_stages"]
        assert after["full_restages"] == before["full_restages"]
        assert_stamped_clean(svc.cache, family, t)
        if (family, event) == ("verdict", "append"):    # see NOTES
            assert svc.cache.integrity["verdict_repairs"] > repairs
        assert svc.cache.integrity["quarantines"] == 0
        # a tear after the next replay is caught before it serves
        svc.cache.fault_injector = inj = FaultInjector(seed=6).add(
            site, kind="corrupt", times=1)
        dml()
        run(svc, pipe, family, t, dim, f"{event} torn replay", rnd=2)
        assert inj.log == [(site, "corrupt")]
        assert svc.cache.integrity["checksum_failures"] == 1
        assert svc.cache.integrity["quarantines"] == 1
        assert_stamped_clean(svc.cache, family, t)
