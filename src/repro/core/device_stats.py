"""Device-resident metadata plane: stage partition stats once, prune forever.

The per-query device path used to re-gather and re-upload a fresh ``[K, P]``
stat slice for every query (a host transpose + H2D copy per launch).  At
fleet scale the pruning *decision* must be as cheap as the paper's headline
makes it look, so the metadata becomes a persistent, index-like device
structure instead of per-query scaffolding (cf. Extensible Data Skipping's
metadata indexes):

  * ``DeviceStatsCache.get`` stages a table's full ``[C, P]`` mins / maxs /
    demote planes to device **once per table version** (keyed like
    ``predicate_cache.TableVersion``) — after that, per-query staging is an
    on-device row gather of the resident arrays, no host work.
  * DML invalidates: ``insert_partitions`` / any version bump produces a
    different key, and the stale entry for the same table is dropped.
  * Eviction is always safe (a miss simply re-stages).
  * Runtime techniques ride the same cache: per-column **join-key planes**
    (``join_key_plane``) and **block-top-k planes** (``block_topk_plane``)
    are staged once per table identity and column, with column-granular
    ``notify_update`` invalidation — see the ``DeviceStatsCache`` class
    docstring.

Precision contract (the single place stats are downcast to f32)
---------------------------------------------------------------
Host metadata is float64; kernels evaluate in float32 for VPU throughput.
Every column has a *primary* row in the ``[C, cap]`` planes, cast by
widening and demoting:

  * partition mins are rounded toward -inf, maxs toward +inf, and query
    lows/highs likewise (lo down, hi up).  Every interval only grows, so
    the kernel can never declare a false NO_MATCH — a pruned partition is
    always truly empty of matches (the correctness-critical direction);
  * wherever a min/max cast was inexact the partition's ``demote`` plane is
    set (same mechanism as nullability), suppressing FULL_MATCH for that
    partition.  Constraints whose lo/hi cast inexactly report
    ``bounds_exact=False`` and the wrapper demotes FULL host-side.

**Wide integer columns are exact.**  An int or dictionary column whose
finite stats are all integers, reach 2**24 (where the f32 cast starts to
move them) and stay within 2**53 (where the host's f64 stops being exact)
is *wide*: Iceberg ``timestamp`` microseconds (~1.7e15), int64 keys.
Staging decides this from the column's own stats (``stat_layout``); no
option names it.  A wide column keeps its widened primary row (the tree
hulls and the join-key overlap read it, and only for NO_MATCH) and adds
2 or 3 *limb rows* after the primary rows: ``v - base`` (``base`` the
staged minimum) as a signed top digit and base-2**23 digits below it
(``stat_limbs``), every one exact in f32 and through the
``Precision.HIGHEST`` one-hot gather.  The limb rows' ``demote`` is the
null flag alone.  A constraint on a wide column splits its snapped query
bounds the same way (``bound_limbs``) and the kernels compare the limbs
lexicographically, so its NO_MATCH, FULL and so LIMIT cut are
bit-identical to the f64 host oracle.  Delta replay keeps the layout
(which columns are wide, their base and digit count); a delta that would
change it restages.

Net effect: integer columns up to 2**53 prune exactly; floats and integer
columns beyond 2**53 can only *false-negative* FULL (degrade to PARTIAL,
costing a scan) and never *false-positive* NO_MATCH or FULL.  The join-key,
enumeration and block-top-k planes keep the widening contract for every
column.  ``tests/test_device_plane.py`` and ``tests/test_wide_columns.py``
hold the regression tests.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import zlib
from collections import OrderedDict
from typing import Callable, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..spans import span
from .metadata import NO_MATCH, PartitionStats
from .predicate_cache import TableVersion


class PlaneIntegrityError(RuntimeError):
    """A restaged plane failed checksum verification again.

    Raised only after the quarantine protocol exhausted its one restage:
    a resident plane's checksum mismatched, the plane was dropped and
    restaged from host truth, and the fresh plane mismatched too (i.e.
    the corruption source is persistent).  The serving layer's
    degradation ladder treats this like any launch failure and demotes —
    a wrong verdict is never served from a plane that failed its stamp.
    """


def plane_checksum(arrays) -> int:
    """Cheap integrity stamp over a plane chunk's bytes (crc32).

    Works identically on host numpy and device arrays (device arrays are
    copied back to host — callers stamp from the *host* arrays at stage
    time for free and only pay the D2H on the sampled verify schedule).
    f32/i32 values round-trip the H2D copy bit-exactly, so a clean plane
    always verifies.
    """
    c = 0
    with span("planes.verify",
              bytes=sum(int(getattr(a, "nbytes", 0)) for a in arrays)):
        for a in arrays:
            c = zlib.crc32(np.ascontiguousarray(np.asarray(a)).tobytes(), c)
    return c

_F32_NEG = np.float32(-np.inf)
_F32_POS = np.float32(np.inf)
_F32_MAX = np.float32(np.finfo(np.float32).max)


def round_down_f32(x: np.ndarray) -> np.ndarray:
    """f64 -> f32 rounding toward -inf (result <= x always)."""
    x = np.asarray(x, dtype=np.float64)
    f = x.astype(np.float32)
    with np.errstate(over="ignore", invalid="ignore"):
        return np.where(f.astype(np.float64) > x, np.nextafter(f, _F32_NEG), f)


def round_up_f32(x: np.ndarray) -> np.ndarray:
    """f64 -> f32 rounding toward +inf (result >= x always)."""
    x = np.asarray(x, dtype=np.float64)
    f = x.astype(np.float32)
    with np.errstate(over="ignore", invalid="ignore"):
        return np.where(f.astype(np.float64) < x, np.nextafter(f, _F32_POS), f)


def cast_stats_f32(
    mins: np.ndarray, maxs: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Widening downcast of stat planes; returns (mins32, maxs32, inexact).

    ``inexact`` is True wherever either bound moved — those partitions must
    never be declared FULL (fed into the demote plane alongside nulls).

    The planes are additionally clamped to the finite f32 extremes: the
    batched kernel gathers stat rows via a one-hot matmul, and a 0-weight
    x inf product would poison the row with NaN.  Clamping ±inf narrows
    the interval, so clamped entries are marked inexact (FULL-demoted);
    NO_MATCH stays safe because ``cast_bounds_f32`` clamps query bounds
    with the same monotone map, keeping every comparison's two sides
    consistent.  All-null partitions' empty intervals survive as
    (+f32max, -f32max) — still empty.
    """
    mins32 = round_down_f32(mins).astype(np.float32)
    maxs32 = round_up_f32(maxs).astype(np.float32)
    inexact = (mins32.astype(np.float64) != mins) | (
        maxs32.astype(np.float64) != maxs)
    mins_c = np.clip(mins32, -_F32_MAX, _F32_MAX)
    maxs_c = np.clip(maxs32, -_F32_MAX, _F32_MAX)
    inexact |= (mins_c != mins32) | (maxs_c != maxs32)
    return mins_c, maxs_c, inexact


def cast_bounds_f32(
    los: np.ndarray, his: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Widening downcast of query range bounds (lo down, hi up).

    Returns (lo32, hi32, exact) where ``exact`` is per-constraint; a False
    entry means FULL must be demoted to PARTIAL for the whole query (the
    widened range may admit rows the true range excludes).

    Bounds are clamped to the finite f32 extremes to match the stat
    planes (see cast_stats_f32).  One-sided infinite bounds lose nothing:
    every clamped stat satisfies ``>= -f32max`` exactly as it satisfied
    ``>= -inf``.  Degenerate lo=+inf / hi=-inf bounds can no longer
    *prove* FULL in the clamped domain, so they are flagged not exact.
    """
    los = np.asarray(los, dtype=np.float64)
    his = np.asarray(his, dtype=np.float64)
    lo32 = round_down_f32(los).astype(np.float32)
    hi32 = round_up_f32(his).astype(np.float32)
    exact = (lo32.astype(np.float64) == los) & (hi32.astype(np.float64) == his)
    exact &= ~np.isposinf(los) & ~np.isneginf(his)
    lo32 = np.clip(lo32, -_F32_MAX, _F32_MAX).astype(np.float32)
    hi32 = np.clip(hi32, -_F32_MAX, _F32_MAX).astype(np.float32)
    return lo32, hi32, exact


# -- wide integer columns: exact limbs ---------------------------------------

LIMB_BITS = 23            # a digit below 2**23, and a top digit of at most
                          # 2**24 in magnitude, are exact in f32
WIDE_MIN = 2.0 ** 24      # below: every integer casts to f32 exactly
WIDE_MAX = 2.0 ** 53      # above: the host's f64 metadata is itself inexact
# Per digit count: the |v - base| the staged stats must keep (a delta past
# it restages), and the clamp of query bounds, strictly beyond every stat
# so a clamped bound compares with each stat as the true bound does.
_STAT_LIM = {2: 1 << 46, 3: 1 << 55}
_BOUND_LIM = {2: 1 << 47, 3: 1 << 56}


@dataclasses.dataclass(frozen=True)
class WideColumn:
    """Where a wide column's limb rows sit in the planes, most significant
    first, and the offset they are taken from."""

    cid: int
    base: int
    limbs: int
    row: int


def column_class(kind: str, mins: np.ndarray, maxs: np.ndarray) -> str:
    """``"float"``, ``"narrow"`` (every value exact in f32), ``"wide"``
    (integral, reaching 2**24, within 2**53) or ``"beyond"`` (an integer
    column the f64 host stats cannot hold exactly either)."""
    if kind == "float":
        return "float"
    v = np.concatenate([mins[np.isfinite(mins)], maxs[np.isfinite(maxs)]])
    if not v.size or np.abs(v).max() < WIDE_MIN:
        return "narrow"
    if np.abs(v).max() <= WIDE_MAX and np.array_equal(v, np.floor(v)):
        return "wide"
    return "beyond"


def stat_layout(stats: PartitionStats
                ) -> Tuple[Tuple[WideColumn, ...], Tuple[int, ...]]:
    """(wide, beyond): the table's wide columns with their limb rows
    (after the C primary rows), and the ids of its integer columns beyond
    2**53 — decided from the columns' own stats."""
    wide, beyond = [], []
    row = stats.num_columns
    for c, meta in enumerate(stats.columns):
        mn, mx = stats.mins[:, c], stats.maxs[:, c]
        cls = column_class(meta.kind, mn, mx)
        if cls == "beyond":
            beyond.append(c)
        if cls != "wide":
            continue
        fin = np.concatenate([mn[np.isfinite(mn)], mx[np.isfinite(mx)]])
        base, top = int(fin.min()), int(fin.max())
        limbs = 2 if top - base < _STAT_LIM[2] else 3
        wide.append(WideColumn(c, base, limbs, row))
        row += limbs
    return tuple(wide), tuple(beyond)


def _split(off: np.ndarray, limbs: int) -> np.ndarray:
    """int64 offsets -> [limbs, n] f32 digits: a signed top digit (floor
    division) and base-2**23 digits below it, all exact in f32."""
    shift = LIMB_BITS * (limbs - 1)
    top = off >> shift
    rem = off - (top << shift)
    out = np.empty((limbs, off.size), dtype=np.float32)
    out[0] = top
    for i in range(1, limbs):
        out[i] = (rem >> (LIMB_BITS * (limbs - 1 - i))) & ((1 << LIMB_BITS) - 1)
    return out


def stat_limbs(values: np.ndarray, w: WideColumn) -> np.ndarray:
    """A wide column's stat values -> [limbs, n] f32 limb rows.

    Exact: the caller keeps every finite value integral and within
    ``_STAT_LIM`` of ``w.base``.  Non-finite stats (empty and dropped
    partitions) take ±f32max in every limb, the planes' drop sentinel,
    so an empty interval stays empty under the lexicographic compare."""
    v = np.asarray(values, dtype=np.float64)
    fin = np.isfinite(v)
    out = _split(np.where(fin, v, w.base).astype(np.int64) - w.base, w.limbs)
    return np.where(fin, out, np.where(v > 0, _F32_MAX, -_F32_MAX)
                    ).astype(np.float32)


def bound_limbs(values: np.ndarray, w: WideColumn) -> np.ndarray:
    """Snapped (integral) query bounds on a wide column -> [limbs, n] f32.

    Bounds beyond every stat (infinite ones included) clamp to
    ``_BOUND_LIM``, which keeps each comparison with a stat as it was, so
    the split is exact."""
    v = np.clip(np.asarray(values, dtype=np.float64), -2.0 ** 62, 2.0 ** 62)
    lim = _BOUND_LIM[w.limbs]
    off = np.clip(v.astype(np.int64) - w.base, -lim, lim)
    return _split(off, w.limbs)


def limb_rows(stats: PartitionStats, wide: Tuple[WideColumn, ...],
              lo: int, hi: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host (mins, maxs, demote) limb rows of partitions [lo, hi), one
    block per wide column in ``wide`` order; demote is the null flag."""
    ms, xs, ds = [], [], []
    for w in wide:
        ms.append(stat_limbs(stats.mins[lo:hi, w.cid], w))
        xs.append(stat_limbs(stats.maxs[lo:hi, w.cid], w))
        nul = (stats.null_counts[lo:hi, w.cid] > 0).astype(np.float32)
        ds.append(np.broadcast_to(nul, (w.limbs, hi - lo)))
    return (np.concatenate(ms), np.concatenate(xs), np.concatenate(ds))


def limbs_fit(stats: PartitionStats, wide: Tuple[WideColumn, ...],
              beyond: Tuple[int, ...], sel) -> bool:
    """Whether the rows ``sel`` (a slice or ids) keep the staged layout:
    every wide column stays integral and within its limbs' reach of its
    base, and no narrow integer column reaches 2**24 (a fresh stage would
    make it wide).  ``beyond`` columns stay as they are."""
    wide_of = {w.cid: w for w in wide}
    for c, meta in enumerate(stats.columns):
        if meta.kind == "float" or c in beyond:
            continue
        v = np.concatenate([stats.mins[sel, c], stats.maxs[sel, c]])
        v = v[np.isfinite(v)]
        if not v.size:
            continue
        w = wide_of.get(c)
        if w is None:
            if np.abs(v).max() >= WIDE_MIN and np.abs(v).max() <= WIDE_MAX:
                return False
            continue
        if np.abs(v).max() > WIDE_MAX or not np.array_equal(v, np.floor(v)):
            return False
        if np.abs(v - w.base).max() >= _STAT_LIM[w.limbs]:
            return False
    return True


def snap_bounds_integral(
    los: np.ndarray, his: np.ndarray, integral: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Tighten range bounds on integral-domain columns: lo -> ceil, hi -> floor.

    Int columns and dictionary codes only take integer (or, for unseen
    string literals, never-attained half-integer) values, so ``x > 5``
    lowered to ``lo = nextafter(5)`` is exactly ``lo = 6`` — an integer
    that (below 2**24) casts to f32 exactly, keeping the device path
    identical to the f64 host oracle on the paper's workloads instead of
    conservatively demoting FULL.  No-op on float columns and on the
    infinite padding sentinels.
    """
    los = np.asarray(los, dtype=np.float64)
    his = np.asarray(his, dtype=np.float64)
    integral = np.asarray(integral, dtype=bool)
    los = np.where(integral & np.isfinite(los), np.ceil(los), los)
    his = np.where(integral & np.isfinite(his), np.floor(his), his)
    return los, his


def plane_capacity(p: int) -> int:
    """Padded partition capacity for delta-staged planes.

    Next power of two with at least 25% append headroom over ``p``, so a
    streaming table absorbs many appends before a capacity overflow
    forces a full restage.  Capacity slots beyond the logical partition
    count hold drop sentinels — every batched kernel treats them as
    never-matching, so no reshape is needed when partitions arrive.
    """
    want = max(8, p + max(p // 4, 1))
    cap = 8
    while cap < want:
        cap *= 2
    return cap


@dataclasses.dataclass(frozen=True)
class PlaneEpoch:
    """What a resident plane reflects: (table version, live count, capacity).

    The service and the technique executors carry this alongside batched
    launches so a delta-staged launch is checkable against (and stays
    bit-identical to) a fresh host restage of the same table version.
    """

    version: int
    live: int
    capacity: int


@dataclasses.dataclass
class DeviceStats:
    """A table's resident metadata plane: [C, cap] device arrays, f32.

    ``capacity >= logical_p``; columns ``logical_p..capacity`` (and
    dropped partitions inside ``logical_p``) hold the drop sentinel
    ``(+f32max, -f32max, demote=1)`` — an empty interval that every
    batched kernel evaluates as NO_MATCH / no-hit / no contribution.

    The three arrays live in ONE ``planes`` tuple swapped atomically by
    delta replay (single attribute store under the GIL), so a launch
    that unpacked the tuple once can never see post-DML mins next to
    pre-DML maxs — the same discipline ``_PlaneEntry.arrays`` follows.
    """

    table_name: str
    version: int           # table DML version the planes reflect
    # ((mins, maxs, demote), logical_p): the three [C, cap] f32 arrays —
    # mins widened toward -inf, maxs toward +inf, demote 1.0 where
    # nulls/inexact cast (no FULL) — bundled with the logical partition
    # count they reflect.  Launch code must read THIS field once
    # (``planes, P = dstats.planes_state; mins, maxs, demote = planes``)
    # rather than the per-array / num_partitions properties, which are
    # separate reads a concurrent replay could tear across.
    planes_state: Tuple
    integral: np.ndarray   # [C] bool, host-side: int/dictionary-code column
    # the staged layout, fixed for this entry's life (a delta that would
    # change it restages a new entry): wide columns' limb rows, and the
    # integer columns beyond 2**53 that keep the widening contract
    wide: Tuple[WideColumn, ...] = ()
    beyond: Tuple[int, ...] = ()
    live_count: int = -1
    tv_version: Optional[int] = None   # service TableVersion seen at staging
    # integrity stamp over the planes' bytes, computed host-side at stage
    # time and re-stamped after every delta replay; the cache verifies it
    # on a sampled read schedule and always after an eviction-restage
    checksum: Optional[int] = None

    def __post_init__(self):
        planes, p = self.planes_state
        if p < 0:          # dense staging: infer logical P from the arrays
            self.planes_state = (planes, int(planes[0].shape[1]))
        if self.live_count < 0:
            self.live_count = self.logical_p

    @property
    def planes(self) -> Tuple:
        return self.planes_state[0]

    @property
    def logical_p(self) -> int:
        return self.planes_state[1]

    @property
    def mins(self) -> jnp.ndarray:
        return self.planes[0]

    @property
    def maxs(self) -> jnp.ndarray:
        return self.planes[1]

    @property
    def demote(self) -> jnp.ndarray:
        return self.planes[2]

    @property
    def num_columns(self) -> int:
        """Primary rows: one per column (the wide columns' limb rows
        follow them)."""
        return len(self.integral)

    @property
    def num_partitions(self) -> int:
        return self.logical_p

    @property
    def capacity(self) -> int:
        return int(self.mins.shape[1])

    @property
    def epoch(self) -> PlaneEpoch:
        return PlaneEpoch(self.version, self.live_count, self.capacity)

    @property
    def primary_planes(self) -> Tuple:
        """The planes' primary rows: what the tree hulls aggregate (a
        hull of limbs is no hull of values)."""
        C = self.num_columns
        if int(self.mins.shape[0]) == C:
            return self.planes
        return tuple(a[:C] for a in self.planes)

    @property
    def nbytes(self) -> int:
        return int(sum(int(a.nbytes) for a in self.planes))

    def limb_slots(self, cids: np.ndarray, lo64: np.ndarray,
                   hi64: np.ndarray, lo32: np.ndarray, hi32: np.ndarray,
                   valid: np.ndarray):
        """The kernels' constraint slots for packed ``[Qb, Kb]`` ranges.

        Returns ``(rows, lo, hi, wide, limbs)``: ``limbs`` slots per
        constraint (1 when no valid constraint is on a wide column, and
        then the slots are ``cids, lo32, hi32`` themselves), the slot
        tables ``[Qb, Kb * limbs]``, and ``wide`` ``[Qb, Kb]`` marking the
        constraints compared on limbs — exact, so never FULL-demoted.  A
        wide constraint's slots are its limb rows and ``bound_limbs`` of
        its snapped f64 bounds; every other constraint keeps its primary
        row and widened bounds in the first slot, row -1 and bounds 0 in
        the others (its compare is the plain one)."""
        wide_of = {w.cid: w for w in self.wide}
        wide = valid & np.isin(cids, list(wide_of))
        if not wide.any():
            return cids, lo32, hi32, wide, 1
        used = [wide_of[c] for c in np.unique(cids[wide]).tolist()]
        limbs = max(w.limbs for w in used)
        Qb, Kb = cids.shape
        rows = np.full((Qb, Kb, limbs), -1, dtype=np.int32)
        lo = np.zeros((Qb, Kb, limbs), dtype=np.float32)
        hi = np.zeros((Qb, Kb, limbs), dtype=np.float32)
        rows[..., 0], lo[..., 0], hi[..., 0] = cids, lo32, hi32
        for w in used:
            m = wide & (cids == w.cid)
            rows[m, :w.limbs] = w.row + np.arange(w.limbs)
            lo[m, :w.limbs] = bound_limbs(lo64[m], w).T
            hi[m, :w.limbs] = bound_limbs(hi64[m], w).T
        flat = (Qb, Kb * limbs)
        return (rows.reshape(flat), lo.reshape(flat), hi.reshape(flat),
                wide, limbs)

    def gather(self, cids: np.ndarray):
        """On-device row gather -> per-constraint [K, cap] planes.

        This replaces the old host transpose + H2D copy per query; the
        resident [C, cap] arrays never leave the device.
        """
        cids = jnp.asarray(np.asarray(cids, dtype=np.int32))
        mins, maxs, demote = self.planes
        return (jnp.take(mins, cids, axis=0),
                jnp.take(maxs, cids, axis=0),
                jnp.take(demote, cids, axis=0))

    @staticmethod
    def stage(stats: PartitionStats, table_name: str = "",
              version: int = 0, capacity: Optional[int] = None,
              live: Optional[np.ndarray] = None) -> "DeviceStats":
        """Host [P, C] f64 stats -> device [C, cap] f32 planes (one H2D copy).

        ``capacity=None`` stages dense (exact [C, P] — the classic
        one-shot path); the cache passes ``plane_capacity(P)`` so the
        staged planes absorb appended partitions in place.
        """
        P = stats.num_partitions
        cap = P if capacity is None else max(int(capacity), P)
        mins32, maxs32, inexact = cast_stats_f32(stats.mins.T, stats.maxs.T)
        demote = ((stats.null_counts.T > 0) | inexact).astype(np.float32)
        wide, beyond = stat_layout(stats)
        if wide:
            with span("planes.limbs", cols=len(wide),
                      bytes=3 * 4 * P * sum(w.limbs for w in wide)):
                lm, lx, ld = limb_rows(stats, wide, 0, P)
            mins32 = np.concatenate([mins32, lm])
            maxs32 = np.concatenate([maxs32, lx])
            demote = np.concatenate([demote, ld])
        if cap > P:
            C = mins32.shape[0]
            pad = cap - P
            mins32 = np.concatenate(
                [mins32, np.full((C, pad), _F32_MAX, np.float32)], axis=1)
            maxs32 = np.concatenate(
                [maxs32, np.full((C, pad), -_F32_MAX, np.float32)], axis=1)
            demote = np.concatenate(
                [demote, np.ones((C, pad), np.float32)], axis=1)
        integral = np.array([c.kind != "float" for c in stats.columns],
                            dtype=bool)
        live_count = P if live is None else int(np.asarray(live, bool).sum())
        return DeviceStats(
            table_name=table_name,
            version=version,
            planes_state=((jnp.asarray(mins32), jnp.asarray(maxs32),
                           jnp.asarray(demote)), P),
            integral=integral,
            wide=wide,
            beyond=beyond,
            live_count=live_count,
            # stamped from the host arrays pre-H2D: free at stage time
            checksum=plane_checksum((mins32, maxs32, demote)),
        )


KPLANE = 64   # block-top-k plane width: values kept per partition

# Hierarchical (tree) plane geometry.  The flat [C, cap] planes aggregate
# into [C, G] *group* planes (G = cap / fanout; both powers of two, so the
# division is exact) — group g's interval is the min/max hull of its
# members, so a query range that misses the hull misses every member: the
# batched kernels can prune whole groups before touching leaves (the
# paper's Sec. 3.2/4.3 adaptive tree, device-resident).  A second, tiny
# *coarse* level (at most TREE_COARSE_MAX root groups) lives host-side in
# the same plane entry: it both restricts the fine pre-pass (log-depth
# refinement) and prices the pre-pass before launching it (the >50%-dense
# fallback).  Below fanout * TREE_MIN_GROUPS partitions the flat launch
# wins and the tree path is skipped entirely.
TREE_FANOUT = 256
TREE_MIN_GROUPS = 4
TREE_COARSE_MAX = 64

# Registry of plane families under the integrity protocol.  Every family
# in DeviceStatsCache._stores MUST be declared here and vice versa — the
# contract linter (tools/contract_lint, rule CL002) enforces the parity,
# so a new family cannot ship without joining checksum stamping and byte
# accounting.  ``verdict`` is the Sec. 8.2 predicate/verdict cache: one
# int8 [cap] three-valued verdict row per (table, canonical predicate).
PLANE_FAMILIES = ("stat", "join_key", "enum", "block_topk", "tree_stat",
                  "verdict")


def coarse_from_groups(gmins, gmaxs) -> Tuple[np.ndarray, np.ndarray]:
    """Host [C, G2] root hull of the [C, G] group planes (G2 <= 64)."""
    gm = np.asarray(gmins)
    gx = np.asarray(gmaxs)
    C, G = gm.shape
    g2 = min(G, TREE_COARSE_MAX)
    f2 = G // g2
    cmins = gm.reshape(C, g2, f2).min(axis=2)
    cmaxs = gx.reshape(C, g2, f2).max(axis=2)
    return cmins, cmaxs


def aggregate_tree_planes(mins, maxs, demote, fanout: int) -> Tuple:
    """Aggregate flat [C, cap] planes into the tree plane arrays.

    Returns ``(gmins, gmaxs, gdem, cmins, cmaxs)``: device [C, G] group
    hulls (min of member mins / max of member maxs / max of member
    demotes) plus the host coarse root level.  Sentinel slots
    (+f32max, -f32max) aggregate to an empty hull only when the whole
    group is sentinels — a live member's interval always widens the hull,
    so group NO_MATCH implies member NO_MATCH with no special-casing.
    """
    C, cap = mins.shape
    if fanout <= 0 or cap % fanout:
        raise ValueError(f"fanout {fanout} must divide plane capacity {cap}")
    G = cap // fanout
    gmins = mins.reshape(C, G, fanout).min(axis=2)
    gmaxs = maxs.reshape(C, G, fanout).max(axis=2)
    gdem = demote.reshape(C, G, fanout).max(axis=2)
    cmins, cmaxs = coarse_from_groups(gmins, gmaxs)
    return gmins, gmaxs, gdem, cmins, cmaxs


@dataclasses.dataclass
class _PlaneEntry:
    """A resident per-column plane: device arrays + the version staged.

    ``arrays`` are capacity-padded along the partition axis (axis 0);
    slots beyond ``logical_p`` and dropped partitions hold the family's
    sentinel.  ``meta`` carries host-side extras (enum wmax/domain_ok).
    """

    version: int
    logical_p: int
    arrays: Tuple
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def capacity(self) -> int:
        return int(self.arrays[0].shape[0])

    @property
    def nbytes(self) -> int:
        return int(sum(int(a.nbytes) for a in self.arrays))


def tree_entry_for(dstats: "DeviceStats", fanout: int = TREE_FANOUT,
                   version: int = 0,
                   logical_p: Optional[int] = None) -> _PlaneEntry:
    """Build a standalone hierarchical plane entry from a flat entry.

    Benchmarks and tests that stage ``DeviceStats`` directly (no table /
    cache) use this to get the same entry shape ``tree_plane`` serves:
    group + coarse arrays in ``arrays``, geometry in ``meta``.  The
    cache's build path delegates here so the two can never drift.
    """
    arrays = aggregate_tree_planes(*dstats.primary_planes, fanout=fanout)
    return _PlaneEntry(
        version,
        dstats.num_partitions if logical_p is None else int(logical_p),
        arrays,
        meta=dict(fanout=fanout, cap=dstats.capacity,
                  groups=int(arrays[0].shape[1])))


@dataclasses.dataclass
class _Resident:
    """A plane the memory manager accounts for: device bytes + pin count."""

    nbytes: int
    pins: int = 0


class PlaneMemoryManager:
    """HBM accountant for every resident plane family, LRU under a budget.

    The paper's fleet serves *thousands* of tables; planes staged
    unboundedly run device memory out long before that.  The manager
    enforces one byte budget across all four plane families (stat,
    join-key, enum, block-top-k) with per-(table, plane) LRU eviction —
    the skewed, shifting table popularity of real fleets (cf.
    Workload-Aware Incremental Reclustering) is exactly the regime LRU
    serves well — plus in-flight pinning so a batched launch can never
    have a plane it is about to consume evicted from under it.

    Contract (the eviction invariants the fleet suite pins):

      * entries with ``pins > 0`` are never selected for eviction;
      * an admit first evicts LRU unpinned entries until the new entry
        fits, so ``bytes_in_use`` exceeds the budget only when the
        *pinned* set alone forces it (counted: ``over_budget_events``,
        ``pin_denied``) — with a sane budget both stay 0;
      * re-admitting a key that was previously evicted counts a
        ``restage_storm`` — the thrash signal for budget sizing;
      * eviction is always *safe*: the owning cache drops the entry (a
        later miss re-stages from host truth), and in-flight launches
        keep their device arrays alive via ordinary references.

    ``budget_bytes=None`` disables eviction but keeps the accounting —
    the unbounded engine reports the same counters, all zeros but
    ``bytes_in_use``/``hits``/``misses``.
    """

    MONOTONIC = ("hits", "misses", "evictions", "evicted_bytes",
                 "restage_storms", "over_budget_events", "pin_denied")
    GAUGES = ("bytes_in_use", "peak_bytes", "pinned_bytes", "budget_bytes",
              "resident_planes")

    def __init__(self, budget_bytes: Optional[int] = None):
        self.budget_bytes = budget_bytes
        # (family, key) -> _Resident, LRU order (oldest first)
        self._resident: "OrderedDict[Tuple, _Resident]" = OrderedDict()
        self._evict_cb: Optional[Callable[[str, Tuple], None]] = None
        self._ever_evicted: set = set()
        # pins owed by scopes whose entry was released (invalidate) and
        # possibly re-admitted under the same key: their unpins consume
        # this debt instead of stripping a NEW scope's pin on the fresh
        # record (which would let it be evicted mid-launch)
        self._orphan_pins: dict = {}
        self.bytes_in_use = 0
        self.peak_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.evicted_bytes = 0
        self.restage_storms = 0
        self.over_budget_events = 0   # admits that left use > budget (pins)
        self.pin_denied = 0           # evictions blocked: all-pinned tail

    def bind(self, evict_cb: Callable[[str, Tuple], None]) -> None:
        """Register the owning cache's store-removal callback."""
        self._evict_cb = evict_cb

    # -- accounting ------------------------------------------------------

    def touch(self, family: str, key: Tuple) -> None:
        """A getter served this resident plane: LRU refresh + hit."""
        fk = (family, key)
        if fk in self._resident:
            self.hits += 1
            self._resident.move_to_end(fk)

    def admit(self, family: str, key: Tuple, nbytes: int) -> None:
        """Account a freshly staged plane, evicting LRU unpinned entries
        first so the budget holds wherever pins allow it to."""
        fk = (family, key)
        old = self._resident.pop(fk, None)
        if old is not None:
            self.bytes_in_use -= old.nbytes
        self.misses += 1
        if fk in self._ever_evicted:
            self.restage_storms += 1
        self._make_room(int(nbytes))
        self._resident[fk] = _Resident(int(nbytes),
                                       pins=old.pins if old else 0)
        self.bytes_in_use += int(nbytes)
        self.peak_bytes = max(self.peak_bytes, self.bytes_in_use)
        if self.budget_bytes is not None \
                and self.bytes_in_use > self.budget_bytes:
            self.over_budget_events += 1

    def _make_room(self, incoming: int) -> None:
        if self.budget_bytes is None:
            return
        if incoming > self.budget_bytes:
            # A plane that can never fit: evicting the whole fleet's
            # residency first would buy nothing — admit over budget
            # (counted by the caller) and leave everyone else resident.
            return
        while self.bytes_in_use + incoming > self.budget_bytes:
            victim = next((fk for fk, r in self._resident.items()
                           if r.pins == 0), None)
            if victim is None:
                # blocked by pins — or, with nothing resident at all, by
                # a single plane larger than the budget (that is an
                # over_budget_event, not pin pressure)
                if self._resident:
                    self.pin_denied += 1
                return
            self._evict_one(victim)

    def _evict_one(self, fk: Tuple) -> None:
        r = self._resident.pop(fk)
        assert r.pins == 0, f"evicting pinned plane {fk}"
        self.bytes_in_use -= r.nbytes
        self.evictions += 1
        self.evicted_bytes += r.nbytes
        self._ever_evicted.add(fk)
        if self._evict_cb is not None:
            self._evict_cb(*fk)

    def was_evicted(self, family: str, key: Tuple) -> bool:
        """Whether this key has ever been budget-evicted — the cache
        force-verifies the checksum on every restage of such a key."""
        return (family, key) in self._ever_evicted

    def release(self, family: str, key: Tuple) -> None:
        """The cache dropped this entry itself (invalidate / restage)."""
        fk = (family, key)
        r = self._resident.pop(fk, None)
        if r is not None:
            self.bytes_in_use -= r.nbytes
            if r.pins:
                # the pinning scopes still owe their unpins — park them
                # as debt so they cannot strip a later scope's pin on a
                # re-admitted record under the same key
                self._orphan_pins[fk] = self._orphan_pins.get(fk, 0) + r.pins

    def reclaim(self) -> None:
        """Evict back under budget once pins release (pin-scope exit).

        A launch whose pinned working set forced an over-budget admit
        leaves ``bytes_in_use > budget`` behind; the owning scope calls
        this on exit so the overshoot lasts exactly as long as the
        launch.  Silent when everything left is pinned by other scopes.
        """
        if self.budget_bytes is None \
                or self.bytes_in_use <= self.budget_bytes:
            return      # common case: every launch exits a scope — O(1)
        # Planes larger than the whole budget can never legally stay:
        # drop them first rather than flushing the rest of the fleet
        # around them (admit leaves them resident only while pinned /
        # until this runs).
        for fk, r in list(self._resident.items()):
            if r.pins == 0 and r.nbytes > self.budget_bytes:
                self._evict_one(fk)
        while self.bytes_in_use > self.budget_bytes:
            victim = next((fk for fk, r in self._resident.items()
                           if r.pins == 0), None)
            if victim is None:
                return
            self._evict_one(victim)

    # -- pinning ---------------------------------------------------------

    def pin(self, family: str, key: Tuple) -> bool:
        r = self._resident.get((family, key))
        if r is None:
            return False
        r.pins += 1
        return True

    def unpin(self, family: str, key: Tuple) -> None:
        fk = (family, key)
        debt = self._orphan_pins.get(fk)
        if debt:                        # our pinned record was released
            if debt == 1:
                del self._orphan_pins[fk]
            else:
                self._orphan_pins[fk] = debt - 1
            return
        r = self._resident.get(fk)
        if r is not None and r.pins > 0:
            r.pins -= 1

    @property
    def pinned_bytes(self) -> int:
        return sum(r.nbytes for r in self._resident.values() if r.pins)

    @property
    def resident_planes(self) -> int:
        return len(self._resident)

    def snapshot(self) -> dict:
        out = {k: getattr(self, k) for k in self.MONOTONIC}
        out.update({k: getattr(self, k) for k in self.GAUGES})
        return out

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        """Monotonic counters differenced, gauges taken from ``after``."""
        out = {k: after[k] - before[k] for k in PlaneMemoryManager.MONOTONIC}
        out.update({k: after[k] for k in PlaneMemoryManager.GAUGES})
        return out


class DeviceStatsCache:
    """Once-per-table staging of metadata planes, delta-synced, LRU-bounded.

    Keys are ``(table_name, stats.uid)``: the stats uid distinguishes a
    *rebuilt* table — same name, same shape, new data — from the object
    that was staged, so a stale plane can never serve it.  Entries of
    dead rebuilt tables age out via the LRU bound.

    Delta staging (incremental ingest)
    ----------------------------------
    Resident entries record the table DML ``version`` they reflect (and
    the service ``TableVersion`` seen at staging).  When a table's
    version advances through its own DML methods (``append_partitions``
    / ``drop_partitions`` / ``update_column``), ``get`` and the
    per-column plane getters *replay* the table's ``TableDelta`` log
    instead of restaging:

      * **append**: planes were allocated with ``plane_capacity`` slack,
        so only the new ``[C, ΔP]`` columns are staged in place;
      * **drop**: dropped partitions are scattered with the no-op
        sentinel ``(+f32max, -f32max, demote=1)`` — all batched kernels
        skip them without any reshape;
      * **update(column)**: the [C, P] planes restage only that column's
        three rows; per-column planes of *other* columns advance their
        version with zero staging work (the satellite-3 guarantee);
      * **rewrite** (or a log gap / capacity overflow): full restage —
        the only cases that pay O(table) again.

    ``staged_bytes`` / ``delta_stages`` / ``full_restages`` count the
    work; ``PruningService.run_batch`` surfaces the per-batch delta via
    ``PruningReport.counters['staging']``.  A version bump *without* a
    covering delta log (legacy ``TableVersion`` bumps) always full
    restages — never wrong, just slower.

    Runtime-technique planes (PR 2)
    -------------------------------
    Alongside the [C, P] min/max/demote planes the cache stages two
    *per-column* plane families for the runtime techniques:

      * **join-key planes** (``join_key_plane``): the key column's widened
        f32 [P] min/max rows, consumed by ``join_overlap_batched``;
      * **enumeration planes** (``enum_plane``): the key column's
        integer-snapped [P] int32 pmin/width rows (width 0 = never
        enumerate), consumed by ``bloom_probe_batched`` for the Bloom
        half of JOIN pruning;
      * **block-top-k planes** (``block_topk_plane``): [P, KPLANE] rows of
        the column's per-partition top-K *signed* values (sign = +1 DESC /
        -1 ASC, nulls excluded, f64 -> f32 rounded toward -inf so every
        stored value is <= the true row value — a boundary derived from
        them is always witnessed), consumed by ``topk_init_batched``.

    Both follow the same TableVersion invalidation discipline through the
    DML hooks, with one refinement: ``on_update(table, column)`` drops the
    [C, P] planes (they carry every column) but only the *matching
    column's* join-key / block-top-k planes — an update to column X cannot
    change column Y's values, so Y's planes stay resident.

    Memory budget (PR 5)
    --------------------
    ``budget_bytes`` hands residency to a ``PlaneMemoryManager``: one
    HBM byte budget across all four plane families, per-(table, plane)
    LRU eviction, and in-flight pinning via ``pin_scope`` so a batched
    launch can never lose a plane it is consuming.  Eviction is always
    safe — a later getter simply restages (and, the plane being gone,
    pays the full-restage cost; the fleet counters make that thrash
    visible as ``restage_storms``).  Without a budget the legacy
    ``max_entries`` / ``max_planes`` count caps apply unchanged.  Every
    getter is atomic under one reentrant lock: the table-version check,
    the delta replay, the manager accounting, and the returned-plane
    read cannot interleave with a concurrent DML invalidation (the
    eviction-path race the fleet suite regression-tests).
    """

    def __init__(self, max_entries: int = 16, max_planes: int = 64,
                 budget_bytes: Optional[int] = None,
                 fault_injector=None, integrity_sample: int = 64,
                 tree_fanout: int = TREE_FANOUT):
        if tree_fanout < 2 or tree_fanout & (tree_fanout - 1):
            raise ValueError(
                f"tree_fanout must be a power of two >= 2, got {tree_fanout}")
        # Leaf partitions per tree-plane group; plane capacities are
        # powers of two with >= 25% headroom, so any pow-2 fanout <= cap
        # divides a table's capacity exactly.
        self.tree_fanout = int(tree_fanout)
        # (name, uid) -> DeviceStats ([C, cap] planes + epoch)
        self.entries: "OrderedDict[Tuple, DeviceStats]" = OrderedDict()  # guarded-by: _lock
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        # (name, uid, col) -> _PlaneEntry((pmin, pmax) [cap] f32 rows)
        self.key_planes: "OrderedDict[Tuple, _PlaneEntry]" = OrderedDict()  # guarded-by: _lock
        # (name, uid, col) -> _PlaneEntry((pmin, width) [cap] i32 rows,
        #                                 meta: wmax, domain_ok)
        self.enum_planes: "OrderedDict[Tuple, _PlaneEntry]" = OrderedDict()  # guarded-by: _lock
        # (name, uid, col, desc, k) -> _PlaneEntry(([cap, k] signed rows,))
        self.topk_planes: "OrderedDict[Tuple, _PlaneEntry]" = OrderedDict()  # guarded-by: _lock
        # (name, uid) -> _PlaneEntry((gmins, gmaxs, gdem) [C, G] device
        # group hulls + (cmins, cmaxs) host coarse root — all five arrays
        # under one CRC stamp; meta: fanout, cap, groups)
        self.tree_planes: "OrderedDict[Tuple, _PlaneEntry]" = OrderedDict()  # guarded-by: _lock
        # (name, uid, canonical predicate key) -> _PlaneEntry((verdicts,))
        # — one int8 [cap] three-valued row; meta: cols (predicate's
        # column reads, for UPDATE invalidation)
        self.verdict_planes: "OrderedDict[Tuple, _PlaneEntry]" = OrderedDict()  # guarded-by: _lock
        self.max_planes = max_planes
        self.plane_hits = 0
        self.plane_misses = 0
        # staging-work counters (H2D bytes; delta vs full attribution)
        self.staged_bytes = 0
        self.delta_stages = 0      # successful delta replays (any family)
        self.full_restages = 0     # full restagings of previously-resident
                                   # planes (rewrite / log gap / overflow)
        self.prefetch_stages = 0   # prefetch() calls that actually staged
                                   # bytes (serving front-end overlap)
        # HBM budget across all plane families.  With a budget set, the
        # byte-LRU memory manager governs residency and the legacy
        # count caps (max_entries / max_planes) are inactive; without
        # one the counts cap as before and the manager only accounts.
        self.memory = PlaneMemoryManager(budget_bytes)
        self._stores = {"stat": self.entries, "join_key": self.key_planes,
                        "enum": self.enum_planes,
                        "block_topk": self.topk_planes,
                        "tree_stat": self.tree_planes,
                        "verdict": self.verdict_planes}
        self.memory.bind(self._evict_family)
        # Epoch check + plane read must be atomic per getter: under the
        # eviction path a concurrent version bump / invalidate between
        # the check and the read could hand out a plane whose manager
        # record is already gone (accounting drift) or mix two versions'
        # arrays.  One reentrant lock serializes getters, DML hooks, and
        # manager mutation; pin scopes are tracked per thread.
        self._lock = threading.RLock()
        self._pin_local = threading.local()
        # Plane integrity (resilience layer): every staged chunk carries
        # a crc32 stamp; reads verify it every ``integrity_sample``-th
        # getter hit (1 = every read — what the chaos suite uses so a
        # corrupted plane can never serve a verdict; 0 = never sample)
        # and ALWAYS right after a quarantine- or eviction-restage.  A
        # mismatch quarantines the plane (drop + one restage from host
        # truth); a second mismatch raises PlaneIntegrityError, which the
        # serving ladder demotes past.  ``fault_injector`` is the chaos
        # seam (serve.resilience.FaultInjector) — None costs one
        # attribute load per site, nothing else.
        self.fault_injector = fault_injector
        self.integrity_sample = int(integrity_sample)
        self._integrity_tick = 0        # guarded-by: _lock
        self._quarantined: set = set()  # guarded-by: _lock
        self.integrity = dict(verifications=0, checksum_failures=0,  # guarded-by: _lock
                              quarantines=0, verdict_repairs=0)

    # ---- memory-manager plumbing ---------------------------------------

    def _evict_family(self, family: str, key: Tuple) -> None:
        """Manager-initiated eviction: drop the entry from its store
        (the manager already removed its own record)."""
        # pop before the fault seam: an injected eviction fault must not
        # leave a store entry whose manager record is already gone
        self._stores[family].pop(key, None)
        if self.fault_injector is not None:
            self.fault_injector.fire("evict")

    # ---- integrity plumbing --------------------------------------------

    def _fire(self, site: str) -> None:
        if self.fault_injector is not None:
            self.fault_injector.fire(site)

    def _corrupt(self, site: str, arrays: Tuple) -> Tuple:
        if self.fault_injector is not None:
            return self.fault_injector.corrupt(site, arrays)
        return arrays

    def _verify_due(self) -> bool:
        s = self.integrity_sample
        if s <= 0:
            return False
        self._integrity_tick += 1
        return self._integrity_tick % s == 0

    def _verify(self, arrays, stamp: Optional[int]) -> bool:
        self.integrity["verifications"] += 1
        return stamp is None or plane_checksum(arrays) == stamp

    def _quarantine(self, family: str, key: Tuple) -> None:
        """A resident plane's bytes no longer match its stamp: count it,
        drop the plane, and mark the key so the restage is verified."""
        self.integrity["checksum_failures"] += 1
        self.integrity["quarantines"] += 1
        self._stores[family].pop(key, None)
        self.memory.release(family, key)
        self._quarantined.add((family, key))

    def integrity_snapshot(self) -> dict:
        with self._lock:
            return dict(self.integrity)

    def _pin_frames(self):
        frames = getattr(self._pin_local, "frames", None)
        if frames is None:
            frames = self._pin_local.frames = []
        return frames

    @contextlib.contextmanager
    def pin_scope(self):
        """Pin every plane a getter returns inside this scope.

        Batched launches wrap their getter + kernel call in a scope so
        the planes they are about to consume cannot be evicted mid-
        launch (and the budget accounting stays honest about in-flight
        HBM).  Scopes nest; pins are reference counts per entry.
        """
        frame: list = []
        with self._lock:
            self._pin_frames().append(frame)
        try:
            yield
        finally:
            # Exception safety is load-bearing here: a raise anywhere in
            # the scope body (failed launch, injected staging fault, an
            # eviction callback blowing up inside reclaim) must still
            # release every pin this frame took, or the leaked refcounts
            # permanently shrink the evictable set under the HBM budget.
            # Every unpin is attempted even if one raises, and reclaim
            # always runs; a reclaim failure (eviction-path fault) may
            # propagate — with zero pins leaked — where the serving
            # ladder treats it like any launch failure.
            with self._lock:
                frames = self._pin_frames()
                # remove by identity: nested scopes can hold equal-content
                # frames, and list.remove's equality match would pop the
                # wrong one, leaking the outer scope's pins forever
                for i in range(len(frames) - 1, -1, -1):
                    if frames[i] is frame:
                        del frames[i]
                        break
                cleanup_exc = None
                for fk in frame:
                    try:
                        self.memory.unpin(*fk)
                    except Exception as exc:  # pragma: no cover - defensive
                        cleanup_exc = exc
                try:
                    self.memory.reclaim()
                except Exception as exc:
                    cleanup_exc = exc
                if cleanup_exc is not None:
                    raise cleanup_exc

    def _scope_pin(self, family: str, key: Tuple) -> None:
        frames = self._pin_frames()
        if frames and self.memory.pin(family, key):
            frames[-1].append((family, key))

    def _touch(self, family: str, key: Tuple) -> None:
        self.memory.touch(family, key)
        self._scope_pin(family, key)

    def _admit(self, family: str, key: Tuple, nbytes: int) -> None:
        self.memory.admit(family, key, nbytes)
        self._scope_pin(family, key)

    # ---- version / delta-log plumbing ----------------------------------

    @staticmethod
    def _table_version(table) -> int:
        return int(getattr(table, "version", 0))

    @staticmethod
    def _deltas_since(table, version: int):
        """Ordered TableDeltas in (version, table.version], or None when
        the log has been compacted past ``version`` (full restage)."""
        deltas = getattr(table, "deltas", None)
        if deltas is None:
            return None
        if version < int(getattr(table, "delta_floor", 0)):
            return None
        return [d for d in deltas if d.version > version]

    @staticmethod
    def _live_count(table) -> int:
        return int(getattr(table, "num_live_partitions",
                           table.stats.num_partitions))

    def staging_snapshot(self) -> dict:
        return dict(staged_bytes=self.staged_bytes,
                    delta_stages=self.delta_stages,
                    full_restages=self.full_restages,
                    prefetch_stages=self.prefetch_stages)

    def prefetch(self, table, tv: Optional[TableVersion] = None) -> bool:
        """Opportunistically stage the table's [C, cap] stat plane ahead
        of its launch — the serving front-end's double-buffer seam: a
        staging thread prefetches batch N+1's planes while batch N's
        launches run lock-free on device.

        Runs the ordinary ``get`` path (epoch check, delta replay,
        checksum stamp, budget accounting — nothing is bypassed), under
        the same reentrant lock, so a concurrent getter simply finds the
        plane already resident.  Never raises: prefetch is advisory, and
        a staging failure here surfaces on the real launch where the
        degradation ladder handles it.  Returns True when bytes were
        actually staged (counted in ``prefetch_stages``).
        """
        with self._lock:
            before = self.staged_bytes
            try:
                self.get(table, tv)
            except Exception:
                return False
            staged = self.staged_bytes > before
            if staged:
                self.prefetch_stages += 1
            return staged

    def plane_epoch(self, table) -> Optional[PlaneEpoch]:
        """The resident [C, cap] plane's epoch for this table, if staged."""
        with self._lock:
            e = self.entries.get((table.name, table.stats.uid))
            return e.epoch if e is not None else None

    # ---- [C, cap] stat planes ------------------------------------------

    @staticmethod
    def _stat_cols(stats: PartitionStats, wide, lo: int, hi: int):
        """Host f32 plane columns for partitions [lo, hi) (delta slice),
        the wide columns' limb rows included."""
        m32, x32, inexact = cast_stats_f32(stats.mins[lo:hi].T,
                                           stats.maxs[lo:hi].T)
        dm = ((stats.null_counts[lo:hi].T > 0) | inexact).astype(np.float32)
        if wide:
            with span("planes.limbs", cols=len(wide),
                      bytes=3 * 4 * (hi - lo) * sum(w.limbs for w in wide)):
                lm, lx, ld = limb_rows(stats, wide, lo, hi)
            m32, x32, dm = (np.concatenate(p) for p in
                            ((m32, lm), (x32, lx), (dm, ld)))
        return m32, x32, dm

    def _replay_stats(self, e: DeviceStats, table, deltas) -> bool:
        """Bring a resident [C, cap] entry current by replaying deltas.

        Returns False when a full restage is required (rewrite delta,
        capacity overflow, unknown kind); on success only the changed
        partition columns were staged.
        """
        with span("planes.stage", P=table.stats.num_partitions):
            stats = table.stats
            if stats.num_partitions > e.capacity:
                return False
            mins, maxs, dem = e.planes
            rows = int(mins.shape[0])
            nbytes = 0
            for d in deltas:
                if d.kind == "append":
                    sl = slice(d.part_lo, d.part_hi)
                    if not limbs_fit(stats, e.wide, e.beyond, sl):
                        return False       # the layout changes: restage
                    m32, x32, dm = self._stat_cols(stats, e.wide, d.part_lo,
                                                   d.part_hi)
                    mins = mins.at[:, sl].set(jnp.asarray(m32))
                    maxs = maxs.at[:, sl].set(jnp.asarray(x32))
                    dem = dem.at[:, sl].set(jnp.asarray(dm))
                    nbytes += int(m32.nbytes + x32.nbytes + dm.nbytes)
                elif d.kind == "drop":
                    ids = jnp.asarray(np.asarray(d.part_ids, dtype=np.int32))
                    mins = mins.at[:, ids].set(_F32_MAX)
                    maxs = maxs.at[:, ids].set(-_F32_MAX)
                    dem = dem.at[:, ids].set(np.float32(1.0))
                    nbytes += 3 * rows * len(d.part_ids) * 4
                elif d.kind == "update":
                    try:
                        ci = stats.col_id(d.column)
                    except KeyError:
                        return False
                    P = stats.num_partitions
                    if not limbs_fit(stats, e.wide, e.beyond, slice(0, P)):
                        return False
                    wide = tuple(w for w in e.wide if w.cid == ci)
                    m32, x32, inexact = cast_stats_f32(
                        stats.mins[:, ci][None, :], stats.maxs[:, ci][None, :])
                    dm = ((stats.null_counts[:, ci][None, :] > 0)
                          | inexact).astype(np.float32)
                    mins = mins.at[ci, :P].set(jnp.asarray(m32[0]))
                    maxs = maxs.at[ci, :P].set(jnp.asarray(x32[0]))
                    dem = dem.at[ci, :P].set(jnp.asarray(dm[0]))
                    nbytes += 3 * P * 4
                    if wide:
                        w = wide[0]
                        with span("planes.limbs", cols=1,
                                  bytes=3 * 4 * P * w.limbs):
                            lm, lx, ld = limb_rows(stats, wide, 0, P)
                        rs = slice(w.row, w.row + w.limbs)
                        mins = mins.at[rs, :P].set(jnp.asarray(lm))
                        maxs = maxs.at[rs, :P].set(jnp.asarray(lx))
                        dem = dem.at[rs, :P].set(jnp.asarray(ld))
                        nbytes += int(lm.nbytes + lx.nbytes + ld.nbytes)
                else:                      # rewrite (or unknown): full restage
                    return False
            # re-stamp from the clean replayed arrays, then let the chaos
            # seam tear bytes *after* the stamp (exactly the corruption the
            # verifier must catch); one atomic tuple store: an in-flight
            # launch that already read e.planes_state keeps a consistent
            # pre-replay (planes, P) pair, and a later read sees the full
            # post-replay pair — never a mix
            e.checksum = plane_checksum((mins, maxs, dem))
            e.planes_state = (self._corrupt("stage.stat", (mins, maxs, dem)),
                              stats.num_partitions)
            e.live_count = self._live_count(table)
            self.staged_bytes += nbytes
            self.delta_stages += 1
            return True

    def get(self, table, tv: Optional[TableVersion] = None) -> DeviceStats:
        """The table's resident DeviceStats: staged on first touch,
        delta-synced on table DML, fully restaged only when it must be.

        stats.uid guards against a rebuilt table (same name, same shape,
        new data) silently hitting the stale staged plane — stale stats
        would break NO_MATCH safety, the one direction that loses rows.
        A service ``TableVersion`` bump without a covering table delta
        log (legacy invalidation flow) also forces a restage.
        """
        with self._lock:
            self._fire("get.stat")
            key = (table.name, table.stats.uid)
            tvv = tv.version if tv is not None else None
            tver = self._table_version(table)
            e = self.entries.get(key)
            if e is not None:
                served = False
                if e.version == tver and (tvv is None or e.tv_version in
                                          (None, tvv)):
                    self.hits += 1
                    if tvv is not None:
                        e.tv_version = tvv
                    served = True
                elif e.version < tver:
                    deltas = self._deltas_since(table, e.version)
                    if deltas is not None and self._replay_stats(e, table,
                                                                 deltas):
                        e.version = tver
                        e.tv_version = tvv
                        self.hits += 1
                        served = True
                if served:
                    self.entries.move_to_end(key)
                    self._touch("stat", key)
                    if not self._verify_due() or self._verify(e.planes,
                                                              e.checksum):
                        return e
                    # sampled verify caught a torn resident plane:
                    # quarantine it and restage fresh below (verified)
                    self._quarantine("stat", key)
                else:
                    # stale and not replayable: rebuild below
                    self.full_restages += 1
                    self.memory.release("stat", key)
            self.misses += 1
            retried = False
            while True:
                self._fire("stage.stat")
                with span("planes.stage", P=table.stats.num_partitions):
                    e = DeviceStats.stage(
                        table.stats, table.name, tver,
                        capacity=plane_capacity(table.stats.num_partitions),
                        live=getattr(table, "live", None))
                e.tv_version = tvv
                planes, logical_p = e.planes_state
                e.planes_state = (self._corrupt("stage.stat", planes),
                                  logical_p)
                self.staged_bytes += e.nbytes
                self._admit("stat", key, e.nbytes)
                self.entries[key] = e
                self.entries.move_to_end(key)
                if self.memory.budget_bytes is None:
                    while len(self.entries) > self.max_entries:
                        k, _ = self.entries.popitem(last=False)
                        self.memory.release("stat", k)
                # a restage of a quarantined or previously-evicted key is
                # ALWAYS verified, whatever the sampling schedule says;
                # other fresh stages join the sampled schedule so a torn
                # stage can't serve its first read unchecked
                force = ("stat", key) in self._quarantined \
                    or self.memory.was_evicted("stat", key) \
                    or self._verify_due()
                if not force or self._verify(e.planes, e.checksum):
                    self._quarantined.discard(("stat", key))
                    return e
                if retried:
                    self._quarantined.discard(("stat", key))
                    self.entries.pop(key, None)
                    self.memory.release("stat", key)
                    raise PlaneIntegrityError(
                        f"stat plane {key} failed checksum verification "
                        f"after quarantine restage")
                self._quarantine("stat", key)
                retried = True

    # ---- runtime-technique planes --------------------------------------

    def _plane_current(self, family: str, store: "OrderedDict", key: Tuple,
                       table, column: str, append_fn, drop_fn):
        """Return the resident plane entry brought current, or None.

        Replays the table's delta log against the entry: appends stage
        only the new partitions (``append_fn``), drops scatter the
        family's sentinel (``drop_fn``), updates of *other* columns are
        free version advances.  An update of ``column`` itself, a
        rewrite, a log gap, or capacity overflow drops the entry (the
        caller stages fresh, counted as a plane miss + full restage).
        """
        e = store.get(key)
        if e is None:
            return None
        tver = self._table_version(table)
        served = False
        if e.version == tver:
            served = True
        elif e.version < tver:
            deltas = self._deltas_since(table, e.version)
            if deltas is not None and \
                    table.stats.num_partitions <= e.capacity:
                ok = True
                staged = False
                nbytes = 0
                with span("planes.stage", P=table.stats.num_partitions):
                    for d in deltas:
                        if d.kind == "append":
                            nbytes += append_fn(e, table, d.part_lo,
                                                d.part_hi)
                            staged = True
                        elif d.kind == "drop":
                            nbytes += drop_fn(e, table, d.part_ids)
                            staged = True
                        elif d.kind == "update" and d.column != column:
                            continue
                        else:
                            ok = False
                            break
                if ok:
                    e.version = tver
                    e.logical_p = table.stats.num_partitions
                    self.staged_bytes += nbytes
                    if staged:
                        self.delta_stages += 1
                        # re-stamp from the clean replayed arrays, then
                        # the chaos seam may tear bytes post-stamp
                        e.meta["checksum"] = plane_checksum(e.arrays)
                        e.arrays = self._corrupt(f"stage.{family}",
                                                 e.arrays)
                    served = True
        if served:
            self.plane_hits += 1
            store.move_to_end(key)
            self._touch(family, key)
            if not self._verify_due() or self._verify(e.arrays,
                                                      e.meta.get("checksum")):
                return e
            # torn resident plane: quarantine; the caller stages fresh
            # (and _plane_fresh force-verifies that restage)
            self._quarantine(family, key)
            return None
        del store[key]
        self.memory.release(family, key)
        self.full_restages += 1
        return None

    def _plane_fresh(self, family: str, store: "OrderedDict", key: Tuple,
                     build_fn) -> _PlaneEntry:
        """Stage a fresh per-column plane with the integrity protocol:
        stamp from the built arrays, admit, and force-verify whenever the
        key was just quarantined or was ever budget-evicted; a verify
        failure quarantines and rebuilds once, a second failure raises
        ``PlaneIntegrityError`` (the serving ladder demotes past it)."""
        retried = False
        while True:
            self._fire(f"stage.{family}")
            with span("planes.stage"):
                e = build_fn()
            e.meta["checksum"] = plane_checksum(e.arrays)
            e.arrays = self._corrupt(f"stage.{family}", e.arrays)
            e = self._plane_put(family, store, key, e)
            fk = (family, key)
            force = fk in self._quarantined \
                or self.memory.was_evicted(family, key) \
                or self._verify_due()
            if not force or self._verify(e.arrays, e.meta["checksum"]):
                self._quarantined.discard(fk)
                return e
            if retried:
                self._quarantined.discard(fk)
                store.pop(key, None)
                self.memory.release(family, key)
                raise PlaneIntegrityError(
                    f"{family} plane {key} failed checksum verification "
                    f"after quarantine restage")
            self._quarantine(family, key)
            retried = True

    def _plane_put(self, family: str, store: "OrderedDict", key: Tuple,
                   entry: _PlaneEntry) -> _PlaneEntry:
        self.plane_misses += 1
        self.staged_bytes += entry.nbytes
        self._admit(family, key, entry.nbytes)
        store[key] = entry
        if self.memory.budget_bytes is None:
            while len(store) > self.max_planes:
                k, _ = store.popitem(last=False)
                self.memory.release(family, k)
        return entry

    # -- join-key planes --

    def _key_rows(self, table, key_col: str, lo: int, hi: int):
        """Widened f32 (pmin, pmax) host rows for partitions [lo, hi)."""
        pmin = np.clip(round_down_f32(table.stats.col_min(key_col)[lo:hi]),
                       -_F32_MAX, _F32_MAX).astype(np.float32)
        pmax = np.clip(round_up_f32(table.stats.col_max(key_col)[lo:hi]),
                       -_F32_MAX, _F32_MAX).astype(np.float32)
        return pmin, pmax

    def _key_append(self, e: _PlaneEntry, table, lo: int, hi: int) -> int:
        pmin, pmax = self._key_rows(table, e.meta["col"], lo, hi)
        a, b = e.arrays
        e.arrays = (a.at[lo:hi].set(jnp.asarray(pmin)),
                    b.at[lo:hi].set(jnp.asarray(pmax)))
        return int(pmin.nbytes + pmax.nbytes)

    def _key_drop(self, e: _PlaneEntry, table, part_ids) -> int:
        ids = jnp.asarray(np.asarray(part_ids, dtype=np.int32))
        a, b = e.arrays
        e.arrays = (a.at[ids].set(_F32_MAX), b.at[ids].set(-_F32_MAX))
        return 2 * len(part_ids) * 4

    def join_key_plane(self, table, key_col: str) -> Tuple:
        """The key column's resident (pmin, pmax) [cap] f32 rows (widened).

        Staged once per (table identity, column) and delta-synced on
        table DML; consumed by the batched join-overlap kernel.  Clamped
        to finite f32 like the [C, cap] planes, so +inf distinct-key
        padding can never produce a hit; dropped/capacity slots hold the
        empty-interval sentinel (+f32max, -f32max) — never a hit either.
        """
        with self._lock:
            self._fire("get.join_key")
            key = (table.name, table.stats.uid, key_col)
            e = self._plane_current("join_key", self.key_planes, key, table,
                                    key_col, self._key_append, self._key_drop)
            if e is not None:
                return e.arrays

            def build():
                P = table.stats.num_partitions
                cap = plane_capacity(P)
                pmin = np.full(cap, _F32_MAX, dtype=np.float32)
                pmax = np.full(cap, -_F32_MAX, dtype=np.float32)
                pmin[:P], pmax[:P] = self._key_rows(table, key_col, 0, P)
                return _PlaneEntry(self._table_version(table), P,
                                   (jnp.asarray(pmin), jnp.asarray(pmax)),
                                   meta=dict(col=key_col))

            return self._plane_fresh("join_key", self.key_planes, key,
                                     build).arrays

    def enum_plane(self, table, key_col: str) -> Tuple:
        """The key column's resident enumeration rows:
        (pmin, width, wmax, domain_ok).

        pmin/width are [P] int32 device rows feeding the Bloom probe
        kernel's narrow-range enumeration: integer-snapped partition
        minima (``ceil(col_min)``) and candidate counts
        (``floor(col_max) - ceil(col_min) + 1``, compared in float64
        before any integer cast so extreme ranges can't overflow).
        width 0 marks partitions that must never be enumerated — empty
        interval, non-finite bounds, or outside int32 (the kernel hashes
        int32 candidates) — and means *keep*: skipping enumeration can
        only miss prunable partitions, never prune joinable ones.  wmax
        (host int) is the plane's max width, used to bucket the kernel's
        enumeration lane dim without a device round-trip.  domain_ok
        (host bool) records whether every non-empty partition's bounds
        sit inside int32 — the device-vs-host parity gate
        (``PruningService.join_device_eligible``), computed once here so
        eligibility never rescans [P] stats per query.

        Same (table identity, column) keying, delta-sync, and
        column-granular invalidation as ``join_key_plane``; width-0 is
        also the drop/capacity sentinel (a dropped partition is never
        enumerated, i.e. kept — which its absence from every scan set
        then makes irrelevant).
        """
        with self._lock:
            self._fire("get.enum")
            key = (table.name, table.stats.uid, key_col)
            e = self._plane_current("enum", self.enum_planes, key, table,
                                    key_col, self._enum_append,
                                    self._enum_drop)
            if e is not None:
                return e.arrays + (e.meta["wmax"], e.meta["domain_ok"])

            def build():
                P = table.stats.num_partitions
                cap = plane_capacity(P)
                pmin_h, width_h, wmax, domain_ok = self._enum_rows(table,
                                                                   key_col)
                pmin = np.zeros(cap, dtype=np.int32)
                width = np.zeros(cap, dtype=np.int32)
                pmin[:P], width[:P] = pmin_h, width_h
                return _PlaneEntry(self._table_version(table), P,
                                   (jnp.asarray(pmin), jnp.asarray(width)),
                                   meta=dict(col=key_col, wmax=wmax,
                                             domain_ok=domain_ok))

            e = self._plane_fresh("enum", self.enum_planes, key, build)
            return e.arrays + (e.meta["wmax"], e.meta["domain_ok"])

    @staticmethod
    def _enum_rows(table, key_col: str):
        """Host enumeration rows over all partitions:
        (pmin i32 [P], width i32 [P], wmax, domain_ok) — exact recompute,
        shared by fresh staging and delta replay (the replay stages only
        the changed slices but refreshes wmax/domain_ok exactly, so the
        delta path choses the same kernel-vs-host route as a fresh one).
        """
        lo = np.ceil(np.asarray(table.stats.col_min(key_col), np.float64))
        hi = np.floor(np.asarray(table.stats.col_max(key_col), np.float64))
        with np.errstate(invalid="ignore", over="ignore"):
            wf = hi - lo + 1.0
            in32 = (lo >= -2.0 ** 31) & (hi < 2.0 ** 31)
            live = np.isfinite(lo) & np.isfinite(hi) & (lo <= hi)
            ok = live & in32 & (wf > 0) & (wf < 2.0 ** 31)
        domain_ok = not bool(np.any(live & ~in32))
        pmin = np.where(ok, lo, 0.0).astype(np.int32)
        width = np.where(ok, wf, 0.0).astype(np.int32)
        wmax = int(width.max()) if width.size else 0
        return pmin, width, wmax, domain_ok

    def _enum_append(self, e: _PlaneEntry, table, lo: int, hi: int) -> int:
        pmin_h, width_h, wmax, domain_ok = self._enum_rows(table,
                                                           e.meta["col"])
        a, b = e.arrays
        e.arrays = (a.at[lo:hi].set(jnp.asarray(pmin_h[lo:hi])),
                    b.at[lo:hi].set(jnp.asarray(width_h[lo:hi])))
        e.meta.update(wmax=wmax, domain_ok=domain_ok)
        return 2 * (hi - lo) * 4

    def _enum_drop(self, e: _PlaneEntry, table, part_ids) -> int:
        ids = jnp.asarray(np.asarray(part_ids, dtype=np.int32))
        a, b = e.arrays
        e.arrays = (a.at[ids].set(np.int32(0)), b.at[ids].set(np.int32(0)))
        _pmin, _width, wmax, domain_ok = self._enum_rows(table,
                                                         e.meta["col"])
        e.meta.update(wmax=wmax, domain_ok=domain_ok)
        return 2 * len(part_ids) * 4

    def block_topk_plane(self, table, order_col: str, desc: bool,
                         k_plane: int = KPLANE) -> jnp.ndarray:
        """The column's resident [P, k_plane] signed block-top-k rows.

        Row p holds partition p's k_plane largest ``sign * value`` entries
        (desc per row, -inf padded, nulls excluded).  Values are rounded
        toward -inf in the signed domain, so every stored entry is <= the
        true value of an actual non-null row — any boundary taken from
        these rows is a *witnessed* Sec. 5.4 boundary.
        """
        with self._lock:
            self._fire("get.block_topk")
            key = (table.name, table.stats.uid, order_col, bool(desc),
                   int(k_plane))
            e = self._plane_current("block_topk", self.topk_planes, key,
                                    table, order_col, self._topk_append,
                                    self._topk_drop)
            if e is not None:
                return e.arrays[0]

            def build():
                P = table.stats.num_partitions
                cap = plane_capacity(P)
                rows = np.full((cap, int(k_plane)), -np.inf,
                               dtype=np.float32)
                rows[:P] = self._topk_rows(table, order_col, bool(desc),
                                           int(k_plane), 0, P)
                return _PlaneEntry(self._table_version(table), P,
                                   (jnp.asarray(rows),),
                                   meta=dict(col=order_col,
                                             desc=bool(desc)))

            return self._plane_fresh("block_topk", self.topk_planes, key,
                                     build).arrays[0]

    @staticmethod
    def _topk_rows(table, order_col: str, desc: bool, k_plane: int,
                   lo: int, hi: int) -> np.ndarray:
        """Signed block-top-k host rows for partitions [lo, hi).

        Rows of dropped partitions are all -inf (the no-contribution
        sentinel): their tombstoned data rows must never witness a
        boundary, and a fresh restage produces the same rows as the
        delta path's sentinel scatter.
        """
        from ..kernels.ops import build_block_topk  # lazy: ops imports us
        sign = 1.0 if desc else -1.0
        sv = round_down_f32(sign * np.asarray(table.data[order_col],
                                              dtype=np.float64))
        nm = table.nulls.get(order_col)
        mask = None if nm is None else ~np.asarray(nm, dtype=bool)
        live = getattr(table, "live", None)
        if live is not None:
            live_rows = np.repeat(np.asarray(live, dtype=bool),
                                  np.diff(table.part_bounds))
            mask = live_rows if mask is None else (mask & live_rows)
        return build_block_topk(sv.astype(np.float32),
                                table.part_bounds[lo:hi + 1],
                                int(k_plane), mask=mask)

    def _topk_append(self, e: _PlaneEntry, table, lo: int, hi: int) -> int:
        (rows,) = e.arrays
        k_plane = int(rows.shape[1])
        new = self._topk_rows(table, e.meta["col"],
                              e.meta["desc"], k_plane, lo, hi)
        e.arrays = (rows.at[lo:hi].set(jnp.asarray(new)),)
        return int(new.nbytes)

    def _topk_drop(self, e: _PlaneEntry, table, part_ids) -> int:
        ids = jnp.asarray(np.asarray(part_ids, dtype=np.int32))
        (rows,) = e.arrays
        e.arrays = (rows.at[ids].set(-jnp.inf),)
        return len(part_ids) * int(rows.shape[1]) * 4

# -- hierarchical (tree) planes --

    def _tree_replay(self, e: _PlaneEntry, table, dstats: DeviceStats,
                     deltas) -> Optional[int]:
        """Re-aggregate only the dirtied groups from the current flat
        planes; returns staged bytes, or None when a full rebuild is
        required (rewrite, unknown delta, unknown column).

        The flat entry ``dstats`` is already current (the caller syncs it
        first), so group hulls re-derive on device with no extra H2D:
        appends dirty only the touched tail groups, drops only the
        dropped ids' groups, and a column update re-aggregates that
        column's group row.  The host coarse level re-derives from the
        group arrays afterwards (one small D2H).
        """
        fanout = e.meta["fanout"]
        gm, gx, gd = e.arrays[:3]
        C, G = int(gm.shape[0]), int(gm.shape[1])
        mins, maxs, dem = dstats.primary_planes
        dirty: set = set()
        rows: set = set()
        for d in deltas:
            if d.kind == "append":
                dirty.update(range(d.part_lo // fanout,
                                   (max(d.part_hi, d.part_lo + 1) - 1)
                                   // fanout + 1))
            elif d.kind == "drop":
                dirty.update(int(p) // fanout
                             for p in np.asarray(d.part_ids).tolist())
            elif d.kind == "update":
                try:
                    rows.add(table.stats.col_id(d.column))
                except KeyError:
                    return None
            else:                  # rewrite (or unknown): full rebuild
                return None
        nbytes = 0
        if dirty:
            gids = np.fromiter(sorted(dirty), dtype=np.int32)
            idx = (gids[:, None].astype(np.int64) * fanout
                   + np.arange(fanout)[None, :]).reshape(-1)
            idx_d = jnp.asarray(idx.astype(np.int32))
            jg = jnp.asarray(gids)
            sm = jnp.take(mins, idx_d, axis=1).reshape(C, len(gids), fanout)
            sx = jnp.take(maxs, idx_d, axis=1).reshape(C, len(gids), fanout)
            sd = jnp.take(dem, idx_d, axis=1).reshape(C, len(gids), fanout)
            gm = gm.at[:, jg].set(sm.min(axis=2))
            gx = gx.at[:, jg].set(sx.max(axis=2))
            gd = gd.at[:, jg].set(sd.max(axis=2))
            nbytes += 3 * C * len(gids) * 4
        for ci in sorted(rows):
            row = slice(0, G * fanout)
            gm = gm.at[ci].set(mins[ci, row].reshape(G, fanout).min(axis=1))
            gx = gx.at[ci].set(maxs[ci, row].reshape(G, fanout).max(axis=1))
            gd = gd.at[ci].set(dem[ci, row].reshape(G, fanout).max(axis=1))
            nbytes += 3 * G * 4
        cmins, cmaxs = coarse_from_groups(gm, gx)
        e.arrays = (gm, gx, gd, cmins, cmaxs)
        return nbytes

    def tree_plane(self, table, dstats: DeviceStats) -> _PlaneEntry:
        """The table's resident hierarchical plane entry, brought current.

        ``dstats`` must be the table's *current* flat entry (from
        ``get``): the tree arrays are pure aggregations of it, so delta
        maintenance re-aggregates dirtied groups from the resident flat
        planes instead of restaging from host truth.  Full member of the
        integrity protocol: CRC-stamped at build and after every replay
        (the stamp covers the host coarse level too — it participates in
        pruning decisions), sampled-verified on read, force-verified
        after quarantine/eviction restage, ``PlaneIntegrityError`` on a
        second failure (the serving ladder demotes to the flat rungs).
        A geometry change (capacity growth, fanout reconfig) rebuilds.
        """
        with self._lock:
            self._fire("get.tree_stat")
            key = (table.name, table.stats.uid)
            fanout = self.tree_fanout
            tver = self._table_version(table)
            e = self.tree_planes.get(key)
            if e is not None:
                served = False
                geometry_ok = (e.meta["fanout"] == fanout
                               and e.meta["cap"] == dstats.capacity)
                if geometry_ok and e.version == tver:
                    served = True
                elif geometry_ok and e.version < tver:
                    deltas = self._deltas_since(table, e.version)
                    if deltas is not None:
                        with span("planes.stage",
                                  P=table.stats.num_partitions):
                            nbytes = self._tree_replay(e, table, dstats,
                                                       deltas)
                        if nbytes is not None:
                            e.version = tver
                            e.logical_p = table.stats.num_partitions
                            self.staged_bytes += nbytes
                            self.delta_stages += 1
                            e.meta["checksum"] = plane_checksum(e.arrays)
                            e.arrays = self._corrupt("stage.tree_stat",
                                                     e.arrays)
                            served = True
                if served:
                    self.plane_hits += 1
                    self.tree_planes.move_to_end(key)
                    self._touch("tree_stat", key)
                    if not self._verify_due() or self._verify(
                            e.arrays, e.meta.get("checksum")):
                        return e
                    self._quarantine("tree_stat", key)
                else:
                    self.tree_planes.pop(key, None)
                    self.memory.release("tree_stat", key)
                    self.full_restages += 1

            def build():
                return tree_entry_for(dstats, fanout=fanout, version=tver,
                                      logical_p=table.stats.num_partitions)

            return self._plane_fresh("tree_stat", self.tree_planes, key,
                                     build)

    # -- verdict planes (Sec. 8.2 predicate cache, device-resident) ------

    def verdict_plane(self, table, pred, ckey: str) -> Optional[np.ndarray]:
        """The cached int8 ``[P]`` verdict row for ``(table, predicate)``,
        brought current — or None on miss (the caller launches the
        ordinary kernel chain and ``verdict_record``s the result).

        ``ckey`` is the canonical predicate key (``expr.canonical_key``),
        so syntactic variants of one predicate share a row.  Full member
        of the integrity protocol: CRC-stamped at record and after every
        delta replay, sampled-verified on read (a torn row quarantines
        and misses — never serves), force-verified on the restage,
        ``PlaneIntegrityError`` on a second failure (the serving ladder
        demotes to the kernel chain: cache-off is a demotion rung, not a
        wrong answer).

        Delta repair from the ``TableDelta`` log: appended partitions are
        the only unknown slots — their verdicts are evaluated host-side
        (f64 ``eval_tv`` over just the ``[part_lo, part_hi)`` stats
        slice, exact, and bit-identical to the device kernels on the
        int/dict exact-f32 domains the parity harness pins) and patched
        in place, counted in ``integrity["verdict_repairs"]``; drops
        scatter the NO_MATCH tombstone sentinel; an UPDATE touching any
        column the predicate reads, a rewrite, a log gap, or capacity
        overflow drops the entry (full miss).
        """
        from .prune_filter import eval_tv  # lazy: avoid import cycles
        with self._lock:
            self._fire("get.verdict")
            key = (table.name, table.stats.uid, ckey)
            e = self.verdict_planes.get(key)
            if e is None:
                return None
            tver = self._table_version(table)
            P = table.stats.num_partitions
            served = False
            if e.version == tver:
                served = True
            elif e.version < tver:
                deltas = self._deltas_since(table, e.version)
                if deltas is not None and P <= e.capacity:
                    row = e.arrays[0]
                    ok = True
                    staged = False
                    nbytes = 0
                    with span("planes.stage", P=P):
                        for d in deltas:
                            if d.kind == "append":
                                sub = table.stats.select(
                                    np.arange(d.part_lo, d.part_hi))
                                patch = eval_tv(pred, sub).astype(np.int8)
                                row = row.at[d.part_lo:d.part_hi].set(
                                    jnp.asarray(patch))
                                self.integrity["verdict_repairs"] += 1
                                nbytes += d.part_hi - d.part_lo
                                staged = True
                            elif d.kind == "drop":
                                ids = jnp.asarray(
                                    np.asarray(d.part_ids, dtype=np.int32))
                                row = row.at[ids].set(np.int8(NO_MATCH))
                                nbytes += len(d.part_ids)
                                staged = True
                            elif d.kind == "update" and \
                                    d.column not in e.meta["cols"]:
                                continue
                            else:   # rewrite / predicate-column update
                                ok = False
                                break
                    if ok:
                        e.arrays = (row,)
                        e.version = tver
                        e.logical_p = P
                        self.staged_bytes += nbytes
                        if staged:
                            self.delta_stages += 1
                            e.meta["checksum"] = plane_checksum(e.arrays)
                            e.arrays = self._corrupt("stage.verdict",
                                                     e.arrays)
                        served = True
            if served:
                self.plane_hits += 1
                self.verdict_planes.move_to_end(key)
                self._touch("verdict", key)
                if not self._verify_due() or self._verify(
                        e.arrays, e.meta.get("checksum")):
                    return np.asarray(e.arrays[0][:P], dtype=np.int8)
                # torn verdict row: quarantine and miss — the relaunch's
                # verdict_record force-verifies the restage
                self._quarantine("verdict", key)
                return None
            del self.verdict_planes[key]
            self.memory.release("verdict", key)
            self.full_restages += 1
            return None

    def verdict_record(self, table, pred, ckey: str,
                       tv_row: np.ndarray) -> None:
        """Stage a freshly-computed verdict row as a resident plane.

        ``tv_row`` is the int8 ``[P]`` three-valued result of a ladder
        rung at or above ``host_oracle`` (exact rungs only — passthrough
        verdicts are uncertified and never recorded).  Capacity-padded
        with the NO_MATCH sentinel like every delta-staged family, so
        appended partitions patch in place.
        """
        with self._lock:
            key = (table.name, table.stats.uid, ckey)
            P = table.stats.num_partitions
            cap = plane_capacity(P)
            row = np.full(cap, NO_MATCH, dtype=np.int8)
            row[:P] = np.asarray(tv_row, dtype=np.int8)
            cols = tuple(pred.columns()) if pred is not None else ()

            def build():
                return _PlaneEntry(self._table_version(table), P,
                                   (jnp.asarray(row),),
                                   meta=dict(cols=cols))

            self._plane_fresh("verdict", self.verdict_planes, key, build)

    def invalidate(self, table_name: str, column: Optional[str] = None
                   ) -> None:
        """Drop staged planes for a table.

        ``column=None`` drops everything (insert/delete semantics); a
        column drops the [C, P] planes (they carry every column's stats)
        plus only that column's join-key / enumeration / block-top-k
        planes.
        """
        with self._lock:
            stale = [k for k in self.entries if k[0] == table_name]
            for k in stale:
                del self.entries[k]
                self.memory.release("stat", k)
            # tree planes aggregate every column, exactly like the [C, P]
            # stat planes they derive from: any invalidation drops them
            stale = [k for k in self.tree_planes if k[0] == table_name]
            for k in stale:
                del self.tree_planes[k]
                self.memory.release("tree_stat", k)
            for family, store in (("join_key", self.key_planes),
                                  ("enum", self.enum_planes),
                                  ("block_topk", self.topk_planes)):
                stale = [k for k in store
                         if k[0] == table_name
                         and (column is None or k[2] == column)]
                for k in stale:
                    del store[k]
                    self.memory.release(family, k)
            # verdict keys carry a canonical predicate, not a column:
            # match on the columns the cached predicate actually reads
            stale = [k for k, e in self.verdict_planes.items()
                     if k[0] == table_name
                     and (column is None or column in e.meta.get("cols", ()))]
            for k in stale:
                del self.verdict_planes[k]
                self.memory.release("verdict", k)

    # ---- DML hooks (mirror predicate_cache's safety analysis; staging a
    # stale stats plane is never *unsafe* for NO_MATCH only if stats were
    # still valid, which DML breaks — so every mutation invalidates) ------

    def on_insert(self, table_name: str) -> None:
        self.invalidate(table_name)

    def on_delete(self, table_name: str) -> None:
        self.invalidate(table_name)

    def on_update(self, table_name: str, column: str) -> None:
        # Updates are column-scoped: the [C, P] stat planes must re-stage
        # (they include the updated column), while the other columns'
        # join-key / enumeration / block-top-k planes remain valid and
        # stay resident.
        self.invalidate(table_name, column=column)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @property
    def resident_bytes(self) -> int:
        # (the enum store used to be summed with a stale 3-tuple unpack
        # that raised once any enum plane was resident; the generic
        # _PlaneEntry walk fixes that)
        with self._lock:
            total = sum(e.nbytes for e in self.entries.values())
            for store in (self.key_planes, self.enum_planes,
                          self.topk_planes, self.tree_planes,
                          self.verdict_planes):
                total += sum(e.nbytes for e in store.values())
            return total
