"""Dropped-partition sentinels through all four batched kernels.

Delta-staged planes tombstone dropped partitions in place — stat rows
``(+f32max, -f32max, demote=1)``, join-key rows the same empty interval,
enumeration width 0, block-top-k rows all -inf — and capacity padding
reuses the identical sentinels.  These tests prove, on the interpret-mode
Pallas kernels AND the jnp/host refs, that sentinel rows are
never-prunable-wrong:

  * live partitions' results are bit-identical with and without sentinel
    rows present (no false NO_MATCH, no lost hits),
  * sentinel partitions themselves come out as skips (NO_MATCH / no
    overlap hit) where skipping is correct, and as keeps (Bloom width-0)
    where only keeping is safe,
  * sentinel block-top-k rows contribute nothing to a Sec. 5.4 boundary
    even when a (buggy) mask selects them.

The sharded classes re-prove all of it through the ``shard_map``
partition-sharded launch path: sentinels are placed on *every shard
edge* (first and last slot of each shard of the plane mesh), where an
off-by-one in shard slicing or a boundary-crossing reduction would
surface — outputs must stay bit-identical to the unsharded launch.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.device_stats import _F32_MAX, DeviceStats, tree_entry_for
from repro.core.metadata import ColumnMeta, PartitionStats
from repro.core.prune_join import BlockedBloom
from repro.kernels import ops

MODES = ("ref", "interpret")

SENT = np.array([0, 3, 7, 11])          # sentinel (dropped) positions
LIVE = np.array([i for i in range(12) if i not in SENT])


def _stats(mins, maxs, C=2):
    P = len(mins)
    return PartitionStats(
        columns=[ColumnMeta(f"c{i}", "int") for i in range(C)],
        mins=np.tile(np.asarray(mins, np.float64)[:, None], (1, C)),
        maxs=np.tile(np.asarray(maxs, np.float64)[:, None], (1, C)),
        null_counts=np.zeros((P, C), dtype=np.int64),
        row_counts=np.full(P, 5, dtype=np.int64),
    )


class TestMinmaxSentinels:
    def test_sentinel_rows_are_no_match_and_live_rows_unchanged(self):
        rng = np.random.default_rng(0)
        base_min = rng.integers(-100, 100, LIVE.size).astype(np.float64)
        base_max = base_min + rng.integers(0, 50, LIVE.size)
        mins = np.full(12, np.inf)
        maxs = np.full(12, -np.inf)      # the drop sentinel, pre-cast
        mins[LIVE], maxs[LIVE] = base_min, base_max
        d_all = DeviceStats.stage(_stats(mins, maxs))
        d_live = DeviceStats.stage(_stats(base_min, base_max))
        range_lists = [
            [(0, -50.0, 75.0)],                      # two-sided
            [(1, 0.0, np.inf)],                      # one-sided lo
            [(0, -np.inf, 10.0)],                    # one-sided hi
            [(0, 42.0, 42.0), (1, -80.0, 120.0)],    # equality + conj
        ]
        for mode in MODES:
            tv = ops.prune_ranges_batched_device(range_lists, d_all,
                                                 mode=mode)
            tv_live = ops.prune_ranges_batched_device(range_lists, d_live,
                                                      mode=mode)
            assert (tv[:, SENT] == 0).all(), mode     # sentinel: NO_MATCH
            np.testing.assert_array_equal(tv[:, LIVE], tv_live,
                                          err_msg=mode)

    def test_capacity_tail_sentinels_sliced_off(self):
        """Capacity-padded staging: the logical slice equals dense."""
        rng = np.random.default_rng(1)
        mins = rng.integers(-100, 100, 10).astype(np.float64)
        maxs = mins + 10
        stats = _stats(mins, maxs)
        padded = DeviceStats.stage(stats, capacity=32)
        dense = DeviceStats.stage(stats)
        assert padded.capacity == 32 and padded.num_partitions == 10
        ranges = [[(0, -200.0, 200.0)], [(1, 0.0, 5.0)]]
        for mode in MODES:
            np.testing.assert_array_equal(
                ops.prune_ranges_batched_device(ranges, padded, mode=mode),
                ops.prune_ranges_batched_device(ranges, dense, mode=mode))


class TestJoinOverlapSentinels:
    def test_empty_interval_never_hits_even_extreme_keys(self):
        rng = np.random.default_rng(2)
        lmin = rng.integers(-1000, 1000, LIVE.size).astype(np.float32)
        lmax = lmin + rng.integers(0, 100, LIVE.size).astype(np.float32)
        pmin = np.full(12, _F32_MAX, dtype=np.float32)
        pmax = np.full(12, -_F32_MAX, dtype=np.float32)
        pmin[LIVE], pmax[LIVE] = lmin, lmax
        distinct = [
            np.sort(rng.integers(-1200, 1200, 9)).astype(np.float32),
            np.array([-_F32_MAX, 0.0, _F32_MAX], dtype=np.float32),
            np.array([_F32_MAX], dtype=np.float32),   # == sentinel pmin
        ]
        for mode in MODES:
            hit = ops.join_overlap_batched_device(
                distinct, jnp.asarray(pmin), jnp.asarray(pmax), mode=mode)
            base = ops.join_overlap_batched_device(
                distinct, jnp.asarray(lmin), jnp.asarray(lmax), mode=mode)
            assert (hit[:, SENT] == 0).all(), mode
            np.testing.assert_array_equal(hit[:, LIVE], base[:, :],
                                          err_msg=mode)


class TestTopKInitSentinels:
    def test_masked_in_sentinel_rows_contribute_nothing(self):
        rng = np.random.default_rng(3)
        K, k = 8, 4
        live_rows = np.sort(
            rng.uniform(-100, 100, (LIVE.size, K)).astype(np.float32),
            axis=1)[:, ::-1]
        plane = np.full((12, K), -np.inf, dtype=np.float32)
        plane[LIVE] = live_rows
        # worst case: the mask wrongly selects every sentinel row too
        mask = np.zeros((3, 12), dtype=np.float32)
        mask[0, :] = 1.0
        mask[1, LIVE[:3]] = 1.0
        mask[1, SENT] = 1.0
        mask[2, SENT] = 1.0                    # only sentinels: empty heap
        base_mask = np.zeros((3, LIVE.size), dtype=np.float32)
        base_mask[0, :] = 1.0
        base_mask[1, :3] = 1.0
        for mode in MODES:
            heap = ops.topk_init_batched_device(
                jnp.asarray(plane), mask, k, mode=mode)
            base = ops.topk_init_batched_device(
                jnp.asarray(live_rows), base_mask, k, mode=mode)
            np.testing.assert_array_equal(heap[:2], base[:2], err_msg=mode)
            assert (heap[2] == -np.inf).all(), mode


class TestBloomProbeSentinels:
    def test_width_zero_sentinel_keeps_and_live_unchanged(self):
        rng = np.random.default_rng(4)
        lmin = rng.integers(0, 500, LIVE.size).astype(np.int32)
        lwidth = rng.integers(1, 12, LIVE.size).astype(np.int32)
        pmin = np.zeros(12, dtype=np.int32)
        width = np.zeros(12, dtype=np.int32)   # width 0 = sentinel = keep
        pmin[LIVE], width[LIVE] = lmin, lwidth
        blooms = []
        for _ in range(3):
            b = BlockedBloom(64)
            b.add(rng.integers(0, 500, 40))
            blooms.append(b)
        wmax = int(lwidth.max())
        for mode in MODES:
            hit = ops.bloom_probe_batched_device(
                blooms, jnp.asarray(pmin), jnp.asarray(width), wmax, 1024,
                mode=mode)
            base = ops.bloom_probe_batched_device(
                blooms, jnp.asarray(lmin), jnp.asarray(lwidth), wmax, 1024,
                mode=mode)
            assert (hit[:, SENT] == 1).all(), mode    # keep: never a false prune
            np.testing.assert_array_equal(hit[:, LIVE], base, err_msg=mode)

    def test_modes_agree(self):
        rng = np.random.default_rng(5)
        pmin = rng.integers(0, 300, 12).astype(np.int32)
        width = rng.integers(0, 9, 12).astype(np.int32)
        b = BlockedBloom(32)
        b.add(rng.integers(0, 300, 20))
        got = [np.asarray(ops.bloom_probe_batched_device(
            [b], jnp.asarray(pmin), jnp.asarray(width),
            int(width.max()), 1024, mode=m)) for m in MODES]
        np.testing.assert_array_equal(got[0], got[1])


# ---------------------------------------------------------------------------
# Sharded launch path: sentinels on every shard edge (interpret + ref)
# ---------------------------------------------------------------------------

def _shard_geometry():
    """(mesh, cap, sentinel ids): EVERY shard's first and last slot is a
    sentinel — including the final shard's trailing slot, the classic
    last-chunk off-by-one position — while each shard keeps live
    interior slots (cap = 4 slots per shard, so 2 live per shard)."""
    if len(jax.devices()) < 2:
        pytest.skip("sharded path needs >= 2 host devices "
                    "(REPRO_CPU_DEVICES forces them; '0' opts out)")
    from repro.kernels.ops import make_plane_mesh
    mesh = make_plane_mesh()
    n = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    cap = max(16, 4 * n)
    assert ops.mesh_shards(mesh, cap) > 1
    s = cap // n
    sent = np.array(sorted({i * s for i in range(n)}
                           | {i * s + s - 1 for i in range(n)}))
    return mesh, cap, sent


class TestShardedMinmaxSentinels:
    def test_edge_sentinels_match_unsharded(self):
        mesh, cap, sent = _shard_geometry()
        rng = np.random.default_rng(6)
        mins = rng.integers(-100, 100, cap).astype(np.float64)
        maxs = mins + rng.integers(0, 50, cap)
        mins[sent], maxs[sent] = np.inf, -np.inf     # drop sentinel, pre-cast
        d = DeviceStats.stage(_stats(mins, maxs))
        ranges = [
            [(0, -50.0, 75.0)],
            [(1, 0.0, np.inf)],
            [(0, 42.0, 42.0), (1, -80.0, 120.0)],
        ]
        for mode in MODES:
            flat = ops.prune_ranges_batched_device(ranges, d, mode=mode)
            tv = ops.prune_ranges_batched_device(ranges, d, mode=mode,
                                                 mesh=mesh)
            np.testing.assert_array_equal(tv, flat, err_msg=mode)
            assert (tv[:, sent] == 0).all(), mode


class TestShardedJoinSentinels:
    def test_edge_sentinels_match_unsharded(self):
        mesh, cap, sent = _shard_geometry()
        rng = np.random.default_rng(7)
        pmin = rng.integers(-1000, 1000, cap).astype(np.float32)
        pmax = pmin + rng.integers(0, 100, cap).astype(np.float32)
        pmin[sent], pmax[sent] = _F32_MAX, -_F32_MAX
        distinct = [
            np.sort(rng.integers(-1200, 1200, 9)).astype(np.float32),
            np.array([-_F32_MAX, 0.0, _F32_MAX], dtype=np.float32),
        ]
        for mode in MODES:
            flat = ops.join_overlap_batched_device(
                distinct, jnp.asarray(pmin), jnp.asarray(pmax), mode=mode)
            hit = ops.join_overlap_batched_device(
                distinct, jnp.asarray(pmin), jnp.asarray(pmax), mode=mode,
                mesh=mesh)
            np.testing.assert_array_equal(hit, flat, err_msg=mode)
            assert (hit[:, sent] == 0).all(), mode


class TestShardedTopKSentinels:
    def test_edge_sentinels_match_unsharded(self):
        mesh, cap, sent = _shard_geometry()
        rng = np.random.default_rng(8)
        K, k = 8, 4
        plane = np.sort(rng.uniform(-100, 100, (cap, K)).astype(np.float32),
                        axis=1)[:, ::-1].copy()
        plane[sent] = -np.inf
        # masks select across shard edges — including only-sentinel rows
        mask = np.zeros((3, cap), dtype=np.float32)
        mask[0, :] = 1.0
        mask[1, sent] = 1.0                       # only sentinels: empty heap
        mask[2, : cap // 2 + 1] = 1.0             # straddles a shard edge
        for mode in MODES:
            flat = ops.topk_init_batched_device(
                jnp.asarray(plane), mask, k, mode=mode)
            heap = ops.topk_init_batched_device(
                jnp.asarray(plane), mask, k, mode=mode, mesh=mesh)
            np.testing.assert_array_equal(heap, flat, err_msg=mode)
            assert (heap[1] == -np.inf).all(), mode


class TestShardedBloomSentinels:
    def test_edge_sentinels_match_unsharded(self):
        mesh, cap, sent = _shard_geometry()
        rng = np.random.default_rng(9)
        pmin = rng.integers(0, 500, cap).astype(np.int32)
        width = rng.integers(1, 12, cap).astype(np.int32)
        pmin[sent], width[sent] = 0, 0            # width 0 = sentinel = keep
        blooms = []
        for _ in range(2):
            b = BlockedBloom(64)
            b.add(rng.integers(0, 500, 40))
            blooms.append(b)
        wmax = int(width.max())
        for mode in MODES:
            flat = ops.bloom_probe_batched_device(
                blooms, jnp.asarray(pmin), jnp.asarray(width), wmax, 1024,
                mode=mode)
            hit = ops.bloom_probe_batched_device(
                blooms, jnp.asarray(pmin), jnp.asarray(width), wmax, 1024,
                mode=mode, mesh=mesh)
            np.testing.assert_array_equal(hit, flat, err_msg=mode)
            assert (hit[:, sent] == 1).all(), mode


# ---------------------------------------------------------------------------
# Hierarchical (tree) plane path: sentinels at the GROUP level (ISSUE 7)
# ---------------------------------------------------------------------------
#
# The group pre-pass aggregates member hulls; a sentinel member
# (+f32max, -f32max) must never widen a hull, a fully-sentinel group's
# empty hull must prune at the group level, and a group that mixes live
# and sentinel members must survive whenever any live member can match.
# Each test proves the tree path bit-identical to the (already
# sentinel-proven) flat path on the same planes, with the pre-pass
# actually engaged (path == 'tree', not a fallback).

TREE_FANOUT = 4
TREE_CAP = 64                      # 16 groups of 4; eligibility needs P>=16
TREE_P = 56                        # live logical slots; 56..63 capacity tail
# group 2 (slots 8..11) fully dropped; singles sit on group edges
TREE_SENT = np.array([0, 8, 9, 10, 11, 19, 20, 34, 55])
TREE_LIVE = np.array([i for i in range(TREE_P) if i not in TREE_SENT])


def _tree_plane_fixture(seed=0, C=2):
    """Clustered stats (sorted mins) so narrow ranges keep few groups."""
    rng = np.random.default_rng(seed)
    mins = np.sort(rng.uniform(-100, 100, TREE_P))
    maxs = mins + rng.uniform(0, 4, TREE_P)
    mins[TREE_SENT], maxs[TREE_SENT] = np.inf, -np.inf
    d = DeviceStats.stage(_stats(mins, maxs, C=C), capacity=TREE_CAP)
    return d, tree_entry_for(d, fanout=TREE_FANOUT), mins, maxs


class TestTreeMinmaxSentinels:
    def test_group_sentinels_bit_identical_to_flat(self):
        d, tree, mins, _ = _tree_plane_fixture()
        lo = float(np.float32(mins[TREE_LIVE[5]]))
        range_lists = [
            [(0, lo, lo + 10.0)],                    # narrow two-sided
            [(1, 80.0, np.inf)],                     # one-sided tail
            [(0, lo, lo), (1, -90.0, -70.0)],        # equality + conj
            [(0, 200.0, 300.0)],                     # misses everything
        ]
        for mode in MODES:
            flat = ops.prune_ranges_batched_device(range_lists, d, mode=mode)
            tv = ops.prune_ranges_batched_tree(range_lists, d, tree,
                                               mode=mode)
            assert ops.last_tree_stats()["path"] == "tree", mode
            np.testing.assert_array_equal(tv, flat, err_msg=mode)
            assert (tv[:, TREE_SENT] == 0).all(), mode

    def test_dense_fallback_is_bit_identical_too(self):
        """A keep-most predicate must fall back flat (coarse density) and
        still agree; the fully-sentinel group stays NO either way."""
        d, tree, _, _ = _tree_plane_fixture(seed=1)
        range_lists = [[(0, -200.0, 200.0)], [(1, -150.0, 150.0)]]
        for mode in MODES:
            flat = ops.prune_ranges_batched_device(range_lists, d, mode=mode)
            tv = ops.prune_ranges_batched_tree(range_lists, d, tree,
                                               mode=mode)
            assert ops.last_tree_stats()["path"] == "flat_dense", mode
            np.testing.assert_array_equal(tv, flat, err_msg=mode)
            assert (tv[:, [8, 9, 10, 11]] == 0).all(), mode


class TestTreeJoinSentinels:
    def test_group_hull_restriction_matches_flat(self):
        d, tree, mins, maxs = _tree_plane_fixture(seed=2)
        # join-key plane: the same widened member intervals, sentinel rows
        # the same empty interval — padded to the plane capacity
        pmin = np.full(TREE_CAP, _F32_MAX, dtype=np.float32)
        pmax = np.full(TREE_CAP, -_F32_MAX, dtype=np.float32)
        pmin[TREE_LIVE] = mins[TREE_LIVE].astype(np.float32)
        pmax[TREE_LIVE] = maxs[TREE_LIVE].astype(np.float32)
        anchor = float(np.float32(mins[TREE_LIVE[8]]))
        distinct = [
            np.sort(np.array([anchor, anchor + 1.0], dtype=np.float32)),
            np.array([_F32_MAX], dtype=np.float32),   # == sentinel pmin
            np.array([-150.0], dtype=np.float32),     # below every hull
        ]
        for mode in MODES:
            flat = ops.join_overlap_batched_device(
                distinct, jnp.asarray(pmin), jnp.asarray(pmax), mode=mode)
            hit = ops.join_overlap_batched_tree(
                distinct, jnp.asarray(pmin), jnp.asarray(pmax), tree, 0,
                mode=mode)
            assert ops.last_tree_stats()["path"] == "tree", mode
            np.testing.assert_array_equal(hit, flat, err_msg=mode)
            assert (hit[:, TREE_SENT] == 0).all(), mode


class TestTreeBloomSentinels:
    def test_width_zero_groups_stay_unconditional_keeps(self):
        d, tree, _, _ = _tree_plane_fixture(seed=3)
        rng = np.random.default_rng(3)
        pmin = np.zeros(TREE_CAP, dtype=np.int32)
        width = np.zeros(TREE_CAP, dtype=np.int32)   # sentinel width 0
        pmin[TREE_LIVE] = rng.integers(0, 500, TREE_LIVE.size)
        width[TREE_LIVE] = rng.integers(1, 12, TREE_LIVE.size)
        blooms = []
        for _ in range(3):
            b = BlockedBloom(64)
            b.add(rng.integers(0, 500, 40))
            blooms.append(b)
        wmax = int(width.max())
        for mode in MODES:
            flat = ops.bloom_probe_batched_device(
                blooms, jnp.asarray(pmin), jnp.asarray(width), wmax, 1024,
                mode=mode)
            hit = ops.bloom_probe_batched_tree(
                blooms, jnp.asarray(pmin), jnp.asarray(width), wmax, 1024,
                tree, mode=mode)
            assert ops.last_tree_stats()["path"] == "tree", mode
            np.testing.assert_array_equal(hit, flat, err_msg=mode)
            # width-0 rows (group 2 is all of them) are unconditional keeps
            assert (np.asarray(hit)[:, [8, 9, 10, 11]] == 1).all(), mode


class TestTreeTopKSentinels:
    def test_compacted_groups_match_flat_heap(self):
        d, tree, _, _ = _tree_plane_fixture(seed=4)
        rng = np.random.default_rng(4)
        K, k = 8, 4
        plane = np.full((TREE_CAP, K), -np.inf, dtype=np.float32)
        plane[TREE_LIVE] = np.sort(
            rng.uniform(-100, 100, (TREE_LIVE.size, K)).astype(np.float32),
            axis=1)[:, ::-1]
        # sparse masks (pre-pass engages) — one selects ONLY the dropped
        # group, whose heap must come back empty, never -f32max garbage
        mask = np.zeros((3, TREE_CAP), dtype=np.float32)
        mask[0, [1, 2, 5, 6, 12, 13]] = 1.0          # two live groups
        mask[1, [8, 9, 10, 11]] = 1.0                # dropped group only
        mask[2, [7, 8]] = 1.0                        # straddles the edge
        for mode in MODES:
            flat = ops.topk_init_batched_device(
                jnp.asarray(plane), mask, k, mode=mode)
            heap = ops.topk_init_batched_tree(
                jnp.asarray(plane), mask, k, tree, mode=mode)
            assert ops.last_tree_stats()["path"] == "tree", mode
            np.testing.assert_array_equal(heap, flat, err_msg=mode)
            assert (np.asarray(heap)[1] == -np.inf).all(), mode
