"""Wide integer columns prune exactly on the device.

An Iceberg ``timestamp`` is a long of microseconds, ~1.7e15 in 2024: far
past float32's 24-bit mantissa, well inside float64's 53 bits.  Staging
holds such a column as exact f32 limbs besides its widened row
(core/device_stats.py precision contract), so the batched filter's
verdicts, FULL marks included, and the LIMIT cuts built on them are
bit-identical to the f64 host oracle — on the flat, tree and sharded
paths, in ``ref`` and ``interpret`` mode, and after delta replay.  Values
beyond 2**53 and float columns keep the widening contract: only sound.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import expr as E
from repro.core.device_stats import (DeviceStats, DeviceStatsCache,
                                     cast_stats_f32, plane_capacity,
                                     tree_entry_for)
from repro.core.flow import PruningPipeline, Query, TableScanSpec
from repro.core.metadata import FULL_MATCH, NO_MATCH, ColumnMeta, PartitionStats
from repro.core.prune_filter import eval_ranges_tv, extract_ranges
from repro.data.table import Table
from repro.kernels import ops
from repro.serve.prune_service import PruningService

T0 = 1_704_067_200_000_000          # 2024-01-01T00:00:00Z in microseconds
SPAN = 31_622_400_000_000           # the year 2024
ROWS = 8
PARTS = 128
FANOUT = 16                         # tree rungs from 4 * 16 partitions

MODES = ["ref", "interpret"]
PATHS = ["flat", "tree"]


def _rows(rng, parts, lo=T0, span=SPAN):
    """Partitions abut: each holds its boundary b_i and b_(i+1) - 1, so a
    literal on one partition's min is 1 us past its neighbour's max (the
    same top limb, told apart by the low one)."""
    n = parts * ROWS
    b = np.sort(rng.choice(span, parts + 1, replace=False)) + lo
    inner = rng.integers(b[:-1, None], b[1:, None], (parts, ROWS - 2))
    ts = np.sort(np.concatenate([b[:-1, None], inner, b[1:, None] - 1],
                                axis=1), axis=1).reshape(-1)
    # neighbours overlap a little, as a clustered table's do
    swap = rng.random(n - 1) < 0.05
    idx = np.arange(n)
    idx[:-1][swap], idx[1:][swap] = idx[1:][swap], idx[:-1][swap]
    return dict(ts=ts[idx].astype(np.int64),
                u=rng.integers(0, 1000, n).astype(np.int64),
                f=rng.random(n))


def _ts_nulls(rng, parts):
    """A few partitions hold a null ts: never FULL, whatever the range."""
    return {"ts": np.repeat(rng.random(parts) < 0.1, ROWS)
            & (rng.random(parts * ROWS) < 0.5)}


def _table(seed=0, parts=PARTS):
    rng = np.random.default_rng(seed)
    return Table.build("ev", _rows(rng, parts), rows_per_partition=ROWS,
                       nulls=_ts_nulls(rng, parts))


def _edge_preds(table, rng, n=24, width=12):
    """Selective ts windows whose literals sit 1 us either side of, or on,
    a partition's min or max."""
    mins = table.stats.col_min("ts")
    maxs = table.stats.col_max("ts")
    live = np.isfinite(mins)
    ids = np.where(live)[0]
    preds = []
    for _ in range(n):
        i = int(rng.choice(ids[:-width]))
        j = int(rng.choice(ids[(ids > i) & (ids <= i + width)]))
        lo = int(mins[i]) + int(rng.integers(-1, 2))
        hi = int(maxs[j]) + int(rng.integers(-1, 2))
        ts = E.col("ts")
        p = (ts > lo - 1) if rng.random() < 0.5 else (ts >= lo)
        p = p & ((ts < hi + 1) if rng.random() < 0.5 else (ts <= hi))
        if rng.random() < 0.5:
            p = p & (E.col("u") >= 10)
        preds.append(p)
    return preds


def _tree_entry(dstats):
    return tree_entry_for(dstats, fanout=FANOUT)


def _launch(path, range_lists, dstats, mode):
    if path == "flat":
        return ops.prune_ranges_batched_device(range_lists, dstats, mode=mode)
    tv = ops.prune_ranges_batched_tree(range_lists, dstats,
                                       _tree_entry(dstats), mode=mode)
    assert ops.last_tree_stats()["path"] == "tree"
    return tv


def _service(path, mode, **kw):
    # a fanout the table is too small for keeps the tree rungs out
    return PruningService(mode=mode, tree_fanout=FANOUT if path == "tree"
                          else 1 << 12, **kw)


def _assert_same_reports(qs, got, want):
    for q, a, b in zip(qs, got, want):
        for name in q.scans:
            np.testing.assert_array_equal(a.scan_sets[name].part_ids,
                                          b.scan_sets[name].part_ids)
            np.testing.assert_array_equal(a.scan_sets[name].match,
                                          b.scan_sets[name].match)
            la = a.per_scan[name].get("limit")
            lb = b.per_scan[name].get("limit")
            assert (la is None) == (lb is None)
            if la is not None:
                assert la.detail == lb.detail
                assert (la.before, la.after) == (lb.before, lb.after)


def test_microsecond_column_stages_as_limbs():
    t = _table()
    d = DeviceStats.stage(t.stats, capacity=plane_capacity(PARTS))
    (w,) = d.wide
    assert (w.cid, w.limbs, w.row) == (t.stats.col_id("ts"), 2, 3)
    assert d.num_columns == 3 and d.mins.shape[0] == 3 + 2
    # the widened primary row moved every ts value; the limbs hold them
    m32, _, inexact = cast_stats_f32(t.stats.mins.T, t.stats.maxs.T)
    assert inexact[w.cid][np.isfinite(t.stats.col_min("ts"))].all()
    np.testing.assert_array_equal(np.asarray(d.mins)[:3, :PARTS], m32)
    limbs = np.asarray(d.mins)[w.row:w.row + 2, :PARTS].astype(np.int64)
    live = np.isfinite(t.stats.col_min("ts"))
    back = w.base + (limbs[0] << 23) + limbs[1]
    np.testing.assert_array_equal(back[live],
                                  t.stats.col_min("ts")[live].astype(np.int64))


def test_narrow_planes_are_unchanged():
    """A table with no wide column stages exactly the planes the
    widening cast alone gives: no limb rows, the same bytes."""
    rng = np.random.default_rng(1)
    raw = dict(a=rng.integers(-5000, 5000, 400).astype(np.int64),
               f=rng.random(400))
    t = Table.build("n", raw, rows_per_partition=8)
    d = DeviceStats.stage(t.stats)
    assert d.wide == () and d.mins.shape[0] == 2
    m32, x32, inexact = cast_stats_f32(t.stats.mins.T, t.stats.maxs.T)
    dem = ((t.stats.null_counts.T > 0) | inexact).astype(np.float32)
    for got, want in zip(d.planes, (m32, x32, dem)):
        assert np.asarray(got).tobytes() == want.tobytes()


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("mode", MODES)
def test_verdicts_bit_identical_to_the_oracle(mode, path):
    t = _table()
    rng = np.random.default_rng(2)
    preds = _edge_preds(t, rng)
    range_lists = [extract_ranges(p, t.stats) for p in preds]
    dstats = DeviceStats.stage(t.stats, capacity=plane_capacity(PARTS))
    tv = _launch(path, range_lists, dstats, mode)
    assert ops.last_pack_stats()["wide"] >= len(preds)
    assert ops.last_pack_stats()["demoted"] == 0
    n_full = 0
    for qi, ranges in enumerate(range_lists):
        oracle = eval_ranges_tv(ranges, t.stats)
        np.testing.assert_array_equal(tv[qi], oracle, err_msg=f"q={qi}")
        n_full += int((oracle == FULL_MATCH).sum())
    assert n_full > 0                 # FULL marks are proven, not demoted


@pytest.mark.parametrize("mode", MODES)
def test_sharded_launch_bit_identical_to_the_oracle(mode):
    """The sharded body on a plane mesh of every host device (one unless
    REPRO_CPU_DEVICES forces more) evaluates the limbs as the flat
    launch does."""
    from repro.kernels.ops import make_plane_mesh
    t = _table(seed=3)
    rng = np.random.default_rng(4)
    range_lists = [extract_ranges(p, t.stats) for p in _edge_preds(t, rng)]
    dstats = DeviceStats.stage(t.stats, capacity=plane_capacity(PARTS))
    _c, _l, _h, full_safe, (rows, lo, hi, limbs) = ops.pack_ranges(
        range_lists, dstats)
    assert limbs == 2 and full_safe.all()
    mesh = make_plane_mesh()
    fn = ops._sharded_minmax(mesh, mode != "ref", mode == "interpret", limbs)
    tv = np.asarray(fn(jnp.asarray(rows), jnp.asarray(lo), jnp.asarray(hi),
                       *dstats.planes))
    if ops.mesh_shards(mesh, dstats.capacity) > 1:
        np.testing.assert_array_equal(
            tv[:len(range_lists), :PARTS],
            ops.prune_ranges_batched_device(range_lists, dstats, mode=mode,
                                            mesh=mesh))
    for qi, ranges in enumerate(range_lists):
        np.testing.assert_array_equal(tv[qi, :PARTS],
                                      eval_ranges_tv(ranges, t.stats))


def _queries(t, rng):
    qs = []
    for i, p in enumerate(_edge_preds(t, rng, n=32, width=6)):
        qs.append(Query(scans={"ev": TableScanSpec(t, p)},
                        limit=int(rng.integers(1, 20)) if i % 2 else None))
    return qs


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("mode", MODES)
def test_limit_cut_equals_the_hosts(mode, path):
    t = _table(seed=5)
    qs = _queries(t, np.random.default_rng(6))
    svc = _service(path, mode)
    got = svc.run_batch(qs)
    want = [PruningPipeline(filter_mode="host").run(q) for q in qs]
    _assert_same_reports(qs, got, want)
    cats = [r.per_scan["ev"]["limit"].detail["category"] for r in got
            if "limit" in r.per_scan["ev"]]
    assert any(c.startswith("pruned_to") for c in cats)
    filt = svc.counters.technique["filter"]
    assert filt["wide"] >= len(qs) and filt["demoted"] == 0
    assert svc.counters.tree_launches == (1 if path == "tree" else 0)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("mode", MODES)
def test_dml_replay_stays_identical(mode, path):
    """Append, drop and rewrite of wide partitions replay into the
    resident planes (no restage) and the reports stay the host's; an
    append the staged limbs cannot reach restages, and stays identical."""
    t = _table(seed=7)
    rng = np.random.default_rng(8)
    svc = _service(path, mode)
    host = PruningPipeline(filter_mode="host")

    def check():
        qs = _queries(t, rng)
        _assert_same_reports(qs, svc.run_batch(qs), [host.run(q) for q in qs])

    check()
    restages = svc.cache.full_restages
    new = _rows(rng, 16, lo=T0 + SPAN, span=SPAN // 8)   # later events
    t.append_partitions(new, rows_per_partition=ROWS)
    svc.notify_append("ev", 16)
    check()
    live = np.where(t.live_mask)[0]
    t.drop_partitions(rng.choice(live, size=6, replace=False))
    svc.notify_drop("ev")
    check()
    assert svc.cache.full_restages == restages
    live = np.where(t.live_mask)[0]
    pid = int(live[5])
    lo = int(t.stats.col_min("ts")[pid])
    t.rewrite_partitions([pid], _rows(rng, 1, lo=lo, span=1_000_000))
    svc.notify_rewrite("ev")
    check()
    # a year of microseconds takes two limbs; 2**47 us later does not fit
    far = _rows(rng, 8, lo=T0 + (1 << 47), span=SPAN)
    t.append_partitions(far, rows_per_partition=ROWS)
    svc.notify_append("ev", 8)
    restages = svc.cache.full_restages
    check()
    assert svc.cache.full_restages > restages
    assert svc.cache.get(t).wide[0].limbs == 3


def test_torn_limb_plane_is_caught():
    """Bytes torn in a limb row after the stamp fail the CRC: the plane
    is quarantined and restaged, and the verdicts stay the oracle's."""
    t = _table(seed=9)
    cache = DeviceStatsCache(integrity_sample=1)
    e = cache.get(t)
    (w,) = e.wide
    planes, P = e.planes_state
    mins = planes[0].at[w.row + 1, 3].add(1.0)
    e.planes_state = ((mins,) + tuple(planes[1:]), P)
    e2 = cache.get(t)
    assert cache.integrity["checksum_failures"] == 1
    assert cache.integrity["quarantines"] == 1
    assert e2 is not e
    range_lists = [extract_ranges(p, t.stats)
                   for p in _edge_preds(t, np.random.default_rng(10))]
    tv = ops.prune_ranges_batched_device(range_lists, e2, mode="ref")
    for qi, ranges in enumerate(range_lists):
        np.testing.assert_array_equal(tv[qi], eval_ranges_tv(ranges, t.stats))


@pytest.mark.parametrize("kind,base", [("int", 2.0 ** 54), ("float", T0 + 0.5)])
def test_beyond_2_53_and_floats_stay_only_sound(kind, base):
    """No limbs for an integer column past 2**53 or a float column: the
    widening contract holds (never a false NO_MATCH or FULL) and FULL is
    demoted where f32 cannot hold a bound."""
    P = 96
    rng = np.random.default_rng(11)
    step = 1 << 20
    mins = base + step * np.arange(P, dtype=np.float64)[:, None]
    maxs = mins + step * rng.integers(0, 3, (P, 1))
    stats = PartitionStats(
        columns=[ColumnMeta("c", kind)], mins=mins, maxs=maxs,
        null_counts=np.zeros((P, 1), dtype=np.int64),
        row_counts=np.full(P, ROWS, dtype=np.int64))
    dstats = DeviceStats.stage(stats)
    assert dstats.wide == ()
    range_lists = []
    for _ in range(24):
        i = int(rng.integers(0, P - 4))
        range_lists.append([(0, float(mins[i, 0]) + 1.0,
                             float(maxs[i + 3, 0]) + 1.0)])
    tv = ops.prune_ranges_batched_device(range_lists, dstats, mode="ref")
    assert ops.last_pack_stats() == dict(wide=0, demoted=len(range_lists))
    demoted = False
    for qi, ranges in enumerate(range_lists):
        oracle = eval_ranges_tv(ranges, stats)
        assert ((tv[qi] == NO_MATCH) <= (oracle == NO_MATCH)).all()
        assert ((tv[qi] == FULL_MATCH) <= (oracle == FULL_MATCH)).all()
        demoted |= bool((tv[qi] != oracle).any())
    assert demoted
