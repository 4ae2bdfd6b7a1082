"""jit'd wrappers wiring the Pallas kernels into the pruning engine.

Each op auto-selects the Pallas kernel on TPU, the interpret-mode kernel
when ``interpret=True`` (CPU validation), or the pure-jnp ref as fallback.
Host-side NumPy metadata is staged to device arrays here; the core engine
(core/*) stays NumPy-pure so compile-time pruning never touches a device.

Device pruning plane (architecture note)
----------------------------------------
Two staging regimes coexist:

  * **Per-query** (``stage_ranges`` / ``prune_ranges_device``): gather the
    ``[K, P]`` stat slice for one query's constraints and launch the
    single-query kernel.  Simple, but every query pays a host transpose +
    H2D copy + launch — fine for one-off queries, wrong for a workload.
  * **Resident + batched** (``prune_ranges_batched_device``): the table's
    full ``[C, P]`` planes live on device in a
    ``core.device_stats.DeviceStatsCache`` (staged once per table
    version); a *batch* of queries is packed into ``[Q, Kb]`` constraint
    tables (Kb a power-of-two bucket, ``(-inf, +inf)`` no-op padding) and
    evaluated by ``minmax_prune_batched`` in one launch, queries on the
    sublane dim.  ``serve.prune_service.PruningService`` is the entry
    point that groups a workload by table and drives this path.

All f32 downcasts go through ``core.device_stats`` (widening + demotion;
see its precision contract).  Integral columns (int / dictionary codes)
get their query bounds snapped to integers first, so the f32 path stays
exactly equal to the f64 host oracle on the paper's workloads; on wide
integer columns (values from 2**24 up to 2**53, e.g. microsecond
timestamps) the batched filter compares exact limbs instead of the
widened values (``pack_ranges``).
"""

from __future__ import annotations

import functools
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PSpec

from ..core.device_stats import (TREE_MIN_GROUPS, DeviceStats,
                                 cast_bounds_f32, cast_stats_f32,
                                 round_down_f32, round_up_f32,
                                 snap_bounds_integral)
from ..core.metadata import PartitionStats
from ..core.prune_join import BLOCK_WORDS
from ..spans import span
from . import ref
from .bloom_probe import bloom_probe_batched
from .join_overlap import join_overlap, join_overlap_batched
from .minmax_prune import minmax_prune
from .minmax_prune_batched import BLOCK_Q, minmax_prune_batched
from .topk_boundary import topk_boundary, topk_init_batched

# Peak elements per gathered [Q, P_slab] plane on the jnp ref path; keeps
# the no-Pallas fallback memory-bounded for huge P without touching the
# kernel (whose grid already tiles P).
_REF_SLAB_ELEMS = 1 << 25


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_interpret(mode: str) -> bool:
    """The ``interpret`` flag of a Pallas launch in ``mode``.

    Only ``mode="interpret"`` interprets.  Any other mode that reaches a
    kernel compiles it for the TPU, and without one it raises instead of
    interpreting in silence: ``"auto"`` never gets here off the TPU (it
    takes the jnp/numpy path), so this guards an explicit ``"pallas"``.
    """
    if mode == "interpret":
        return True
    if not _on_tpu():
        raise RuntimeError(
            f"mode={mode!r} compiles the Pallas kernels for a TPU, but the "
            f"default backend is {jax.default_backend()!r}; use "
            f"mode='interpret' to run them on this backend")
    return False


# ---------------------------------------------------------------------------
# Partition-dim sharding (fleet-scale planes; make_plane_mesh)
# ---------------------------------------------------------------------------
#
# Every batched kernel evaluates queries x partitions with no cross-
# partition coupling except the top-k heap (a pure selection, mergeable by
# rank).  A 1-D ``parts`` mesh therefore shards the resident planes on the
# partition (capacity) dim via shard_map: each device runs the identical
# kernel on its [*, cap/n] shard, verdict rows concatenate, and per-shard
# top-k heaps reduce with the rank-selection merge.  Capacity padding and
# dead-partition sentinels are position-independent no-ops, so a sentinel
# landing on a shard edge behaves exactly as it does mid-plane
# (tests/test_kernel_sentinels.py pins that for all four kernels).

PLANE_AXIS = "parts"


def make_plane_mesh():
    """The host's devices as one 1-D ``PLANE_AXIS`` mesh: the
    partition-shard mesh of the metadata-plane kernels.

    The resident ``[C, P]`` planes split their partition (capacity) dim
    over this axis via ``shard_map``, so a table's P can grow past one
    device's memory.  Plane capacities are powers of two, so the axis is
    the largest power-of-two prefix of the host's device set — every
    capacity >= the axis size divides evenly and shards.  On a
    single-device host the mesh is size 1 and the launch path stays
    unsharded (same code, no shard_map).
    """
    devs = jax.devices()
    n = 1
    while n * 2 <= len(devs):
        n *= 2
    return jax.sharding.Mesh(np.asarray(devs[:n]), (PLANE_AXIS,))


def mesh_shards(mesh, cap: int) -> int:
    """Usable partition-shard count for a capacity-``cap`` plane.

    The mesh's device count when it has a ``parts`` axis dividing ``cap``
    (plane capacities and plane-mesh sizes are both powers of two, so
    this holds for every plane at least as wide as the mesh); otherwise 1
    — the launch simply stays unsharded, same math, one device.
    """
    if mesh is None:
        return 1
    if PLANE_AXIS not in getattr(mesh, "axis_names", ()):
        return 1
    n = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    return n if (n > 1 and cap % n == 0) else 1


def _use_kernel(mode: str) -> bool:
    """Kernel vs jnp-oracle body inside a sharded launch — the same
    mode policy as the unsharded wrappers (``auto`` off-TPU -> oracle)."""
    return mode != "ref" and (mode != "auto" or _on_tpu())


def _body_flags(mode: str) -> Tuple[bool, bool]:
    """(use_kernel, interpret) for a sharded launch's per-shard body."""
    use = _use_kernel(mode)
    return use, use and kernel_interpret(mode)


# Shard count the most recent batched launch on THIS thread actually
# used (1 = unsharded) — the wrappers can demote a mesh-eligible launch
# back to unsharded when the jnp-oracle body's dense footprint exceeds
# the slab bound, and the service's sharded_launches counter must report
# what really ran, not mesh eligibility.  Thread-local so concurrent
# services (the supported multi-threaded serving regime) cannot
# cross-attribute each other's launches.
_shard_note = threading.local()


def last_launch_shards() -> int:
    return getattr(_shard_note, "n", 1)


def _note_shards(n: int) -> int:
    _shard_note.n = int(n)
    return n


# The sharded callables are built once per (mesh, static config) and
# jit-wrapped, so repeated launches hit the jit cache instead of
# re-tracing shard_map eagerly per call — a fleet issues thousands of
# launches over a handful of shape buckets.

@functools.lru_cache(maxsize=None)
def _sharded_minmax(mesh, use_kernel: bool, interp: bool, limbs: int = 1):
    def body(c, l, h, m, x, d):
        if use_kernel:
            return minmax_prune_batched(c, l, h, m, x, d, interpret=interp,
                                        limbs=limbs)
        return ref.minmax_prune_batched_ref(c, l, h, m, x, d, limbs=limbs)

    rep, sp = PSpec(), PSpec(None, PLANE_AXIS)
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(rep, rep, rep, sp, sp, sp),
                                 out_specs=sp, check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_join(mesh, use_kernel: bool, interp: bool):
    def body(d, a, b):
        if use_kernel:
            return join_overlap_batched(d, a, b, interpret=interp)
        return ref.join_overlap_batched_ref(d, a, b)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(PSpec(), PSpec(PLANE_AXIS), PSpec(PLANE_AXIS)),
        out_specs=PSpec(None, PLANE_AXIS), check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_bloom(mesh, use_kernel: bool, interp: bool, enum_pad: int):
    def body(l, h, pm, w):
        if use_kernel:
            return bloom_probe_batched(l, h, pm, w, enum_pad=enum_pad,
                                       interpret=interp)
        return ref.bloom_probe_batched_ref(l, h, pm, w, enum_pad)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(PSpec(), PSpec(), PSpec(PLANE_AXIS), PSpec(PLANE_AXIS)),
        out_specs=PSpec(None, PLANE_AXIS), check_vma=False))


@functools.lru_cache(maxsize=None)
def _sharded_topk(mesh, use_kernel: bool, interp: bool, k: int):
    def body(pl, m):
        if use_kernel:
            heap = topk_init_batched(pl, m, k, interpret=interp)
        else:
            heap = ref.topk_init_batched_ref(pl, m, k)
        return heap[None]

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(PSpec(PLANE_AXIS, None), PSpec(None, PLANE_AXIS)),
        out_specs=PSpec(PLANE_AXIS, None, None), check_vma=False))


def _pow2_at_least(n: int, floor: int = 1) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def k_bucket(k: int) -> int:
    """Constraint-count bucket: next power of two >= max(k, 1).

    Batches are padded up to the bucket with no-op ranges so the batched
    kernel sees a handful of static Kb values, bounding jit recompiles.
    """
    return _pow2_at_least(max(k, 1))


def q_bucket(q: int) -> int:
    """Query-count bucket: next power of two >= max(q, BLOCK_Q)."""
    return _pow2_at_least(max(q, 1), floor=BLOCK_Q)


def d_bucket(d: int) -> int:
    """Distinct-key-count bucket: next power of two >= max(d, 8).

    Batched join overlap pads each query's distinct list up to the bucket
    with +inf no-op keys, so jit recompiles stay bounded — the same scheme
    as ``k_bucket`` for constraint counts.
    """
    return _pow2_at_least(max(d, 1), floor=8)


def bloom_bucket(n_blocks: int) -> int:
    """Bloom block-count bucket: next power of two >= max(n_blocks, 8).

    Filters are *tiled* (not zero-padded) up to the bucket — block
    selection is ``h & (blocks - 1)``, so a periodically repeated filter
    probes identical words under the larger mask (see pack_blooms) —
    and the floor keeps the packed [16, Bb] word planes at full sublane
    height.
    """
    return _pow2_at_least(max(n_blocks, 1), floor=8)


def enum_bucket(w: int) -> int:
    """Enumeration-lane bucket: next power of two >= max(w, 128).

    The Bloom kernel enumerates a partition's candidate values on the
    lane dim; the bucket keeps lanes full (128) and recompiles bounded.
    """
    return _pow2_at_least(max(w, 1), floor=128)


# Kernel-path cap on blocks per Bloom filter: the in-kernel one-hot gather
# materializes a [Bb, E] f32 tile per probe step (4MB at 1024 x 1024 —
# comfortably inside VMEM next to the [16, Bb] word planes).  Bigger
# filters (build NDV > ~32k at 16 bits/key) fall back to the host
# matcher, counted per technique.
BLOOM_MAX_BLOCKS = 1024


# ---------------------------------------------------------------------------
# Per-query staging (single-launch path)
# ---------------------------------------------------------------------------

def _stage_ranges(ranges, stats: PartitionStats):
    """One staging pass: kernel inputs + whether FULL is provable.

    Returns ((lo, hi, mins, maxs, demote) device arrays, full_safe bool).
    The f32 downcast is centralized in core.device_stats: stat intervals
    are widened (mins down, maxs up) and partitions whose cast was inexact
    are FULL-demoted via the nullable/demote plane; full_safe is False
    when any query bound's own cast was inexact.
    """
    cids = np.array([c for c, _, _ in ranges], dtype=np.int64)
    lo64 = np.array([l for _, l, _ in ranges], dtype=np.float64)
    hi64 = np.array([h for _, _, h in ranges], dtype=np.float64)
    integral = np.array([c.kind != "float" for c in stats.columns], dtype=bool)
    lo64, hi64 = snap_bounds_integral(lo64, hi64, integral[cids])
    lo32, hi32, exact = cast_bounds_f32(lo64, hi64)
    mins32, maxs32, inexact = cast_stats_f32(stats.mins.T[cids],
                                             stats.maxs.T[cids])
    demote = ((stats.null_counts.T[cids] > 0) | inexact).astype(np.float32)
    staged = (jnp.asarray(lo32), jnp.asarray(hi32), jnp.asarray(mins32),
              jnp.asarray(maxs32), jnp.asarray(demote))
    return staged, bool(exact.all())


def stage_ranges(
    ranges: List[Tuple[int, float, float]],
    stats: PartitionStats,
):
    """Gather per-constraint stat rows into the kernel's [K, P] layout."""
    staged, _ = _stage_ranges(ranges, stats)
    return staged


def prune_ranges_device(
    ranges: List[Tuple[int, float, float]],
    stats: PartitionStats,
    mode: str = "auto",          # 'auto' | 'pallas' | 'interpret' | 'ref'
) -> np.ndarray:
    """Three-valued conjunctive-range pruning on device; returns tv [P]."""
    if not ranges:   # empty conjunction == TruePred: everything FULL
        return np.full(stats.num_partitions, 2, dtype=np.int8)
    (lo, hi, mins, maxs, nullable), full_safe = _stage_ranges(ranges, stats)
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        tv = ref.minmax_prune_ref(lo, hi, mins, maxs, nullable)
    else:
        tv = minmax_prune(lo, hi, mins, maxs, nullable,
                          interpret=kernel_interpret(mode))
    tv = np.asarray(tv)
    if not full_safe:
        tv = np.minimum(tv, 1)   # inexact f32 bounds: FULL is not provable
    return tv


# ---------------------------------------------------------------------------
# Batched multi-query path (resident metadata plane)
# ---------------------------------------------------------------------------

def pack_ranges(
    range_lists: Sequence[List[Tuple[int, float, float]]],
    dstats: DeviceStats,
    min_k: int = 1,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, Tuple]:
    """Pack per-query constraint lists into [Qb, Kb] kernel inputs.

    Returns (cids int32, lo f32, hi f32, full_safe bool[Q], slots).  The
    first three are the primary rows and widened bounds, which the tree
    path's hull tests read; ``slots`` = (rows, lo, hi, limbs) are what the
    filter kernels evaluate: the same tables when no constraint is on a
    wide column, else ``limbs`` exact slots per constraint
    (``DeviceStats.limb_slots``).  Constraint slots beyond a query's K and
    query rows beyond Q are ``(-inf, +inf)`` no-ops; Kb (at least
    ``min_k``) and Qb are power-of-two buckets so recompiles stay bounded.
    ``last_pack_stats`` then counts
    the batch's constraints compared on limbs (``wide``) and those whose
    bounds f32 could not hold (``demoted``: FULL demoted for the query).
    """
    Q = len(range_lists)
    Kb = k_bucket(max([min_k] + [len(r) for r in range_lists]))
    Qb = q_bucket(Q)
    cids = np.zeros((Qb, Kb), dtype=np.int32)
    valid = np.zeros((Qb, Kb), dtype=bool)
    lo64 = np.full((Qb, Kb), -np.inf, dtype=np.float64)
    hi64 = np.full((Qb, Kb), np.inf, dtype=np.float64)
    for qi, ranges in enumerate(range_lists):
        for ki, (cid, lo_v, hi_v) in enumerate(ranges):
            cids[qi, ki] = cid
            valid[qi, ki] = True
            lo64[qi, ki] = lo_v
            hi64[qi, ki] = hi_v
    lo64, hi64 = snap_bounds_integral(lo64, hi64, dstats.integral[cids])
    lo32, hi32, exact = cast_bounds_f32(lo64, hi64)
    # cast_bounds_f32 clamps to finite f32; re-impose the (-inf, +inf)
    # sentinel on padding slots so the kernel's no-op detection fires.
    lo32 = np.where(valid, lo32, np.float32(-np.inf))
    hi32 = np.where(valid, hi32, np.float32(np.inf))
    rows, slo, shi, wide, limbs = dstats.limb_slots(cids, lo64, hi64, lo32,
                                                    hi32, valid)
    full_safe = (exact | ~valid | wide).all(axis=1)[:Q]
    _pack_note.d = dict(wide=int(wide.sum()),
                        demoted=int((valid & ~exact & ~wide).sum()))
    return cids, lo32, hi32, full_safe, (rows, slo, shi, limbs)


# What the most recent pack on THIS thread found (constraints compared on
# limbs, constraints whose bounds f32 could not hold) — the service's
# filter counters read it after each launch; thread-local like the notes
# below.
_pack_note = threading.local()


def last_pack_stats() -> dict:
    return getattr(_pack_note, "d", {})


# The flat launch's verdict copy spans the live partitions rounded up to
# one of this many granules of the plane's capacity: a handful of widths
# per capacity, one for a fixed P, and appends within a granule reuse it.
VERDICT_GRANULES = 8


def verdict_width(P: int, Pc: int) -> int:
    """Columns of the flat launch's verdicts copied to the host: ``P``
    rounded up to a multiple of ``Pc // VERDICT_GRANULES``, at most
    ``Pc``, then to whole 4-byte words."""
    g = max(1, Pc // VERDICT_GRANULES)
    return -(-min(Pc, -(-max(P, 1) // g) * g) // 4) * 4


@functools.partial(jax.jit, static_argnames=("width",))
def _narrow_verdicts(tv, full_safe, width: int):
    """The flat launch's epilogue, on the device: FULL demoted to PARTIAL
    on the rows whose bounds f32 could not hold, the partition axis cut
    (or, past a capacity that is not whole words, padded with NO_MATCH)
    to ``width``, int32 narrowed to int8 — the form the host consumes,
    which views the result back as ``[Qb, width]`` int8.  The int8 bytes
    travel packed four to an int32 word: the TPU copies int8 arrays to
    the host at about a third of int32's rate per byte.

    A program of its own, not one with the filter's: fused with the jnp
    oracle or the interpreted kernel into one program, an int8 result
    corrupted the heap of XLA's CPU backend."""
    tv = tv[:, :width]
    tv = jnp.pad(tv, ((0, 0), (0, width - tv.shape[1])))
    tv = jnp.where(full_safe[:, None], tv,
                   jnp.minimum(tv, 1)).astype(jnp.int8)
    return jax.lax.bitcast_convert_type(
        tv.reshape(tv.shape[0], width // 4, 4), jnp.int32)


_batched_ref_jit = jax.jit(ref.minmax_prune_batched_ref,
                           static_argnames=("limbs",))


def _fetch(out) -> np.ndarray:
    """A launch's result on the host, in two spans: ``device_wait`` holds
    the device's compute, ``d2h`` only the transfer (and any relayout) of
    the ready result.  ``out`` may be a list of slabs along the last
    axis, which arrive as one array."""
    with span("device_wait"):
        jax.block_until_ready(out)
    if isinstance(out, list):
        with span("d2h", bytes=sum(int(o.nbytes) for o in out)):
            return np.concatenate([np.asarray(o) for o in out], axis=-1)
    with span("d2h", bytes=int(out.nbytes)):
        return np.asarray(out)


def prune_ranges_batched_device(
    range_lists: Sequence[List[Tuple[int, float, float]]],
    dstats: DeviceStats,
    mode: str = "auto",          # 'auto' | 'pallas' | 'interpret' | 'ref'
    mesh=None,                   # 1-D 'parts' mesh: shard the partition dim
) -> np.ndarray:
    """Evaluate Q queries' conjunctive ranges in one batched launch.

    Returns tv ``[Q, P]`` int8 — row q is identical to the f64 host
    oracle on int/dictionary columns up to 2**53 (bounds snap to
    integers; below 2**24 they cast exactly, above it a wide column's
    limbs compare exactly).  Bounds that are inexact in f32 (floats,
    integers beyond 2**53) demote FULL to PARTIAL — never a false
    NO_MATCH or false FULL (core.device_stats precision contract).

    With ``mesh`` (``make_plane_mesh``) the resident planes
    shard on the capacity dim: each device evaluates its partition slice
    and the verdict rows concatenate — bit-identical to the unsharded
    launch (partitions are independent).

    Every rung leaves the device as ``[Qb, verdict_width(P, Pc)]`` int8
    (packed in int32 words) with the FULL demotion applied, and the
    result is a view of that copy, read-only but for the slabbed jnp
    path's.
    """
    Q = len(range_lists)
    with span("pack", q=Q):
        # one consistent snapshot: a concurrent delta replay swaps the
        # whole (planes, logical P) pair atomically, so a single read here
        # can never mix post-DML planes with a pre-DML partition count (or
        # vice versa)
        planes, P = dstats.planes_state
        Pc = int(planes[0].shape[1])   # staged capacity (>= P; sentinel tail)
        W = verdict_width(P, Pc)
        _c, _l, _h, full_safe, (rows, lo, hi, limbs) = pack_ranges(
            range_lists, dstats)
        Qb = rows.shape[0]
        safe = np.ones(Qb, dtype=bool)  # padding rows are sliced off
        safe[:Q] = full_safe
        args = (jnp.asarray(rows), jnp.asarray(lo), jnp.asarray(hi))
        safe_d = jnp.asarray(safe)
    shards = mesh_shards(mesh, Pc)
    if (shards > 1 and not _use_kernel(mode)
            and Qb * Pc // shards > _REF_SLAB_ELEMS):
        shards = 1     # per-shard jnp body would exceed the slab bound;
                       # the unsharded path below slabs instead
    _note_shards(shards)
    with span("dispatch", q=Q, P=P):
        if shards > 1:
            fn = _sharded_minmax(mesh, *_body_flags(mode), limbs)
            out = _narrow_verdicts(fn(*args, *planes), safe_d, width=W)
        elif _use_kernel(mode):
            out = _narrow_verdicts(
                minmax_prune_batched(*args, *planes,
                                     interpret=kernel_interpret(mode),
                                     limbs=limbs), safe_d, width=W)
        else:
            slab = max(1024, _REF_SLAB_ELEMS // Qb)
            if slab >= Pc:
                out = _narrow_verdicts(
                    _batched_ref_jit(*args, *planes, limbs=limbs), safe_d,
                    width=W)
            else:
                out = []
                for s in range(0, W, slab):   # past W: sentinel tail only
                    cut = [jax.lax.slice_in_dim(a, s, min(s + slab, Pc),
                                                axis=1) for a in planes]
                    out.append(_narrow_verdicts(
                        _batched_ref_jit(*args, *cut, limbs=limbs), safe_d,
                        width=min(slab, W - s)))
    tv = _fetch(out)
    with span("unpack", q=Q, P=P):
        return tv.view(np.int8)[:Q, :P]


def prune_ranges_batched_host(
    range_lists: Sequence[List[Tuple[int, float, float]]],
    stats: PartitionStats,
) -> np.ndarray:
    """Pure-numpy host fallback for the batched range kernel.

    The degradation ladder's third rung: same ``[Q, P]`` int8 verdict
    contract as ``prune_ranges_batched_device`` but evaluated directly
    on the host f64 stats — no device, no staged planes, no f32 cast, so
    it is bit-identical to the per-query ``eval_tv`` host oracle on
    every predicate whose ranges lowered (the closed-interval semantics:
    NO when the partition interval misses [lo, hi], FULL when it sits
    inside with no nulls, PARTIAL otherwise; constraints AND via min).
    An empty range list is the TruePred lowering: everything FULL.
    """
    P = stats.num_partitions
    tv = np.full((len(range_lists), P), 2, dtype=np.int8)
    mins, maxs = stats.mins, stats.maxs            # [P, C] float64
    has_nulls = stats.null_counts > 0
    for qi, ranges in enumerate(range_lists):
        row = np.full(P, 2, dtype=np.int8)
        for cid, lo, hi in ranges:
            pmin, pmax = mins[:, cid], maxs[:, cid]
            no = (pmax < lo) | (pmin > hi)
            full = (pmin >= lo) & (pmax <= hi) & ~has_nulls[:, cid]
            row = np.minimum(
                row, np.where(no, 0, np.where(full, 2, 1)).astype(np.int8))
        tv[qi] = row
    return tv


# ---------------------------------------------------------------------------
# Hierarchical (tree) pruning path: group pre-pass + gathered leaf eval
# ---------------------------------------------------------------------------
#
# The flat batched path is linear in P — every query touches every
# partition slot.  The tree path makes the device work proportional to
# *survivors* instead, in three levels (core.device_stats stages the
# aggregated planes; see its tree-geometry note):
#
#   0. host coarse: the [C, G2] root hulls (G2 <= 64) evaluate in numpy —
#      this both restricts level 1 and *prices* the pre-pass before any
#      launch.  Coarse survivors bound fine survivors from above (a dead
#      root kills all its children), so a coarse density over the cutoff
#      proves the fine pre-pass can't win and the flat launch runs with
#      ZERO extra launches — the stale-selectivity guarantee.
#   1. fine group pre-pass: the [C, G] group planes evaluate only at
#      coarse-survivor children, per-query, via the gathered oracle.
#   2. leaf: the flat [C, cap] planes evaluate only at surviving groups'
#      member positions; verdicts scatter into the [Q, P] output.  Every
#      unlisted live partition sits in a group whose hull missed the
#      query, and group NO_MATCH implies member NO_MATCH, so the
#      scattered rows are bit-identical to the flat evaluation.
#
# FULL is never decided above the leaves: sentinel members don't widen a
# hull, so a hull inside [lo, hi] proves nothing about its members — the
# pre-pass only ever decides NO_MATCH vs survive (over-approximation is
# structural, exactly the Extensible-Data-Skipping safety argument).

TREE_DENSE_CUTOFF = 0.5

# What the most recent tree-path launch on THIS thread actually did
# (path taken, group counts, survivor densities) — benches and parity
# tests read it; thread-local like the shard note.
_tree_note = threading.local()


def last_tree_stats() -> dict:
    return getattr(_tree_note, "d", {})


def _note_tree(**kw) -> None:
    _tree_note.d = dict(kw)


_gathered_ref_jit = jax.jit(ref.minmax_prune_gathered_ref,
                            static_argnames=("limbs",))

# Positions per gathered launch of the tree path (a multiple of every
# pow-2 fanout up to it).  Each level launches fixed-width chunks of its
# survivor positions, so a table geometry compiles one shape per level
# however many groups survive: survivor counts vary batch by batch, and a
# width that followed them would compile inside a serving window.  The
# constraint dim has a floor for the same reason: a micro-batch whose
# queries all carry one range would otherwise compile a Kb of its own.
TREE_CHUNK = 1 << 14
TREE_MIN_K = 4


def _coarse_survivors(cids, lo, hi, cmins, cmaxs) -> np.ndarray:
    """surv [Q, G2] bool — host evaluation of the coarse root level.

    Mirrors the NO_MATCH term of the batched oracle (empty-hull and
    range-miss tests); padding no-op slots keep everything."""
    surv = np.ones((cids.shape[0], cmins.shape[1]), dtype=bool)
    for k in range(cids.shape[1]):
        pm = cmins[cids[:, k]]                        # [Q, G2]
        px = cmaxs[cids[:, k]]
        lo_k = lo[:, k][:, None]
        hi_k = hi[:, k][:, None]
        noop = (lo_k == -np.inf) & (hi_k == np.inf)
        no = ((pm > px) | (px < lo_k) | (pm > hi_k)) & ~noop
        surv &= ~no
    return surv


def _survivor_positions(surv: np.ndarray, span: int,
                        width: int) -> np.ndarray:
    """pos [Q, n * width] int32 — each row's surviving ids expanded to
    their ``span`` child positions (id * span + j), right-padded with id
    0's children to the fewest whole chunks of ``width`` columns (a
    multiple of ``span``) that hold the max per-row count.  Padding is
    *exact*, not a sentinel: the gathered evaluator computes the true
    verdict at every listed position, and scattering a truthful verdict
    twice — or for a non-surviving group, whose members are provably NO
    — changes nothing."""
    Q = surv.shape[0]
    counts = surv.sum(axis=1)
    per = width // span
    sb = per * max(1, -(-int(counts.max()) // per))
    ids = np.zeros((Q, sb), dtype=np.int64)
    qs, gs = np.nonzero(surv)
    col = np.arange(len(qs)) - np.repeat(np.cumsum(counts) - counts, counts)
    ids[qs, col] = gs
    pos = (ids[:, :, None] * span
           + np.arange(span, dtype=np.int64)[None, None, :])
    return pos.reshape(Q, sb * span).astype(np.int32)


def _gathered(tables, planes, pos: np.ndarray, width: int, Qb: int,
              limbs: int) -> list:
    """The gathered evaluator over ``pos`` in launches of ``width``
    columns, query rows padded to ``Qb``; chunks are cut on the host, so
    no slice compiles either."""
    Q = pos.shape[0]
    out = []
    for s in range(0, pos.shape[1], width):
        chunk = np.zeros((Qb, width), dtype=np.int32)
        chunk[:Q] = pos[:, s:s + width]
        out.append(_gathered_ref_jit(*tables, *planes, jnp.asarray(chunk),
                                     limbs=limbs))
    return out


def prune_ranges_batched_tree(
    range_lists: Sequence[List[Tuple[int, float, float]]],
    dstats: DeviceStats,
    tree_entry,                  # DeviceStatsCache.tree_plane(...) entry
    mode: str = "auto",
    mesh=None,
    dense_cutoff: float = TREE_DENSE_CUTOFF,
) -> np.ndarray:
    """tv [Q, P] int8 via the hierarchical group pre-pass.

    Bit-identical to ``prune_ranges_batched_device`` row for row (and so
    to the f64 host oracle wherever the flat path is): the pre-pass only
    removes positions whose group hull *proves* NO_MATCH.  The hulls and
    the coarse root aggregate the primary rows, widened f32, so the
    pre-pass reads the widened bounds; the leaves evaluate the kernel's
    exact slots (wide columns' limbs).  Falls back to the flat launch when
    the table is too small for the tree geometry or the coarse survivor
    density exceeds ``dense_cutoff`` — the density check runs on the host
    coarse level, so the dense-workload fallback never pays a pre-pass
    launch.  The gathered evaluations use the jnp oracle on every backend
    (XLA-native gathers; the Pallas kernel remains the flat path's dense
    evaluator) in fixed-width chunks (``TREE_CHUNK``), and are unsharded
    — a mesh is forwarded to the flat fallback only.
    """
    Q = len(range_lists)
    planes, P = dstats.planes_state
    mins, maxs, demote = planes
    Pc = int(mins.shape[1])
    gm, gx, gd = tree_entry.arrays[:3]
    cmins, cmaxs = (np.asarray(a) for a in tree_entry.arrays[3:])
    fanout = int(tree_entry.meta["fanout"])
    G = int(gm.shape[1])
    if Q == 0 or Pc != G * fanout or P < fanout * TREE_MIN_GROUPS:
        _note_tree(path="flat_small", groups=G)
        return prune_ranges_batched_device(range_lists, dstats, mode,
                                           mesh=mesh)
    cids, lo, hi, full_safe, (rows, slo, shi, limbs) = pack_ranges(
        range_lists, dstats, min_k=TREE_MIN_K)
    Qb = cids.shape[0]
    # Level 0 — padding rows beyond Q are all-no-op and survive
    # everything; the density must price only the real rows.
    csurv = _coarse_survivors(cids[:Q], lo[:Q], hi[:Q], cmins, cmaxs)
    G2 = csurv.shape[1]
    # Density over the coarse groups that hold live partitions: the
    # capacity tail (up to half the plane after a power-of-two resize)
    # never survives, and counting it would keep a whole-table predicate
    # under the cutoff.
    live_g2 = -(-P // (Pc // G2))
    cdens = csurv.sum(axis=1).max() / max(live_g2, 1)
    if cdens > dense_cutoff:
        _note_tree(path="flat_dense", groups=G, coarse_density=float(cdens))
        return prune_ranges_batched_device(range_lists, dstats, mode,
                                           mesh=mesh)
    # Level 1 — fine group pre-pass over coarse-survivor children only,
    # on the widened primary tables.
    f2 = G // G2
    width1 = max(min(G, TREE_CHUNK), f2)
    with span("pack", q=Q):
        prim = (jnp.asarray(cids), jnp.asarray(lo), jnp.asarray(hi))
        gpos = _survivor_positions(csurv, f2, width1)
    with span("dispatch", q=Q, P=G):
        out = _gathered(prim, (gm, gx, gd), gpos, width1, Qb, 1)
    tvg = _fetch(out)
    with span("unpack", q=Q, P=G):
        tvg = tvg[:Q]
        gsurv = np.zeros((Q, G), dtype=bool)
        qrow = np.repeat(np.arange(Q), gpos.shape[1])
        gsurv[qrow, gpos.reshape(-1)] = (tvg > 0).reshape(-1)
        fdens = gsurv.sum(axis=1).max() / G
    # Level 2 — gathered leaf evaluation of the kernel's slots over
    # surviving groups' members, in chunks no wider than the flat ref
    # path's slab (slab and chunk are pow-2 multiples of fanout).
    with span("pack", q=Q):
        groups_per_slab = max(1, (_REF_SLAB_ELEMS // max(Qb, 1)) // fanout)
        slab = fanout * (1 << (groups_per_slab.bit_length() - 1))
        width2 = max(fanout, min(Pc, TREE_CHUNK, slab))
        pos = _survivor_positions(gsurv, fanout, width2)   # [Q, n * width2]
        W = pos.shape[1]
        slots = prim if limbs == 1 else (jnp.asarray(rows), jnp.asarray(slo),
                                         jnp.asarray(shi))
    with span("dispatch", q=Q, P=P):
        out = _gathered(slots, (mins, maxs, demote), pos, width2, Qb, limbs)
    tvl = _fetch(out)
    _note_shards(1)
    with span("unpack", q=Q, P=P):
        tvl = tvl[:Q]
        # Scatter — unlisted positions stay 0 (NO): every unlisted live
        # partition sits in a pruned group, and group NO implies member NO.
        tv = np.zeros((Q, P), dtype=np.int8)
        ps = pos.reshape(-1)
        live = ps < P                    # capacity-tail sentinel slots
        qs = np.repeat(np.arange(Q), W)[live]
        tv[qs, ps[live]] = tvl.reshape(-1)[live].astype(np.int8)
        if not full_safe.all():
            tv[~full_safe] = np.minimum(tv[~full_safe], 1)
    _note_tree(path="tree", groups=G, coarse_density=float(cdens),
               fine_density=float(fdens), leaf_cols=int(W))
    return tv


def join_overlap_batched_tree(
    distinct_lists: Sequence[np.ndarray],
    pmin: jnp.ndarray,
    pmax: jnp.ndarray,
    tree_entry,
    key_ci: int,
    mode: str = "auto",
    part_ids_lists: Optional[Sequence[np.ndarray]] = None,
    mesh=None,
    dense_cutoff: float = TREE_DENSE_CUTOFF,
) -> np.ndarray:
    """hit [Q, P] — group pre-pass wrapper over the batched join overlap.

    The stat tree's ``key_ci`` row is a hull over the same widened f32
    member intervals as the join-key plane (both derive from the same
    ``round_down/round_up + clamp`` of the same f64 column stats), so a
    distinct list that misses group g's hull misses every member: those
    members' hits are provably 0 and drop out of the part-id restriction
    handed to the flat evaluator.  Bit-identical either way; the kernel
    path ignores part-id restrictions by design (dense resident
    evaluation), so the win lands on the no-Pallas fallback.
    """
    Q = len(distinct_lists)
    P = int(pmin.shape[0])
    fanout = int(tree_entry.meta["fanout"])
    G = int(tree_entry.meta["groups"])
    if Q == 0 or P > G * fanout:
        _note_tree(path="flat_small", groups=G)
        return join_overlap_batched_device(distinct_lists, pmin, pmax, mode,
                                           part_ids_lists, mesh)
    hg_lo = np.asarray(tree_entry.arrays[0])[key_ci]      # [G] group hulls
    hg_hi = np.asarray(tree_entry.arrays[1])[key_ci]
    restricted = []
    dens = 0.0
    for qi, d in enumerate(distinct_lists):
        d32 = np.asarray(d, dtype=np.float32)
        # group g may hit iff some distinct key lands in its hull; an
        # empty hull (all-sentinel group) brackets nothing.
        ghit = (np.searchsorted(d32, hg_hi, side="right")
                > np.searchsorted(d32, hg_lo, side="left"))
        dens = max(dens, ghit.sum() / G)
        ids = (np.arange(P) if part_ids_lists is None
               else np.asarray(part_ids_lists[qi]))
        restricted.append(ids[ghit[ids // fanout]])
    if dens > dense_cutoff:
        _note_tree(path="flat_dense", groups=G, fine_density=float(dens))
        return join_overlap_batched_device(distinct_lists, pmin, pmax, mode,
                                           part_ids_lists, mesh)
    _note_tree(path="tree", groups=G, fine_density=float(dens))
    return join_overlap_batched_device(distinct_lists, pmin, pmax, mode,
                                       restricted, mesh)


def bloom_probe_batched_tree(
    blooms: Sequence,
    pmin: jnp.ndarray,
    width: jnp.ndarray,
    wmax: int,
    enum_limit: int,
    tree_entry,
    mode: str = "auto",
    part_ids_lists: Optional[Sequence[np.ndarray]] = None,
    mesh=None,
) -> np.ndarray:
    """hit [Q, P] — group pre-pass wrapper over the batched Bloom probe.

    Bloom pruning only ever decides partitions that are *enumerable*
    (0 < width <= enum_limit); everything else is an unconditional keep.
    The group pre-pass aggregates enumerability over the width plane
    (one host reshape over the resident view — no launch) and restricts
    the part-id lists to members of groups with at least one enumerable
    member.  The restriction covers every enumerable partition, so the
    excluded rows are exactly the flat path's unconditional keeps —
    bit-identical.
    """
    Q = len(blooms)
    P = int(pmin.shape[0])
    fanout = int(tree_entry.meta["fanout"])
    G = int(tree_entry.meta["groups"])
    w = np.asarray(width)
    if Q == 0 or int(w.shape[0]) != G * fanout:
        _note_tree(path="flat_small", groups=G)
        return bloom_probe_batched_device(blooms, pmin, width, wmax,
                                          enum_limit, mode, part_ids_lists,
                                          mesh)
    genum = ((w > 0) & (w <= enum_limit)).reshape(G, fanout).any(axis=1)
    restricted = []
    for qi in range(Q):
        ids = (np.arange(P) if part_ids_lists is None
               else np.asarray(part_ids_lists[qi]))
        restricted.append(ids[genum[ids // fanout]])
    _note_tree(path="tree", groups=G, fine_density=float(genum.mean()))
    return bloom_probe_batched_device(blooms, pmin, width, wmax, enum_limit,
                                      mode, restricted, mesh)


def topk_init_batched_tree(
    plane: jnp.ndarray,
    mask: np.ndarray,
    k: int,
    tree_entry,
    mode: str = "auto",
    mesh=None,
    dense_cutoff: float = TREE_DENSE_CUTOFF,
) -> np.ndarray:
    """heap [Q, k] — group-compacted wrapper over the batched top-k init.

    The union of the candidate masks' groups names every plane row any
    query can select from, so evaluating the compacted [S * fanout, K]
    plane slice with compacted masks returns identical value multisets
    (top-k is a pure selection; masked-out rows contribute nothing).
    Dense unions fall back flat; the compacted capacity rarely divides a
    plane mesh, so the compacted launch runs unsharded.
    """
    mask = np.asarray(mask)
    Q = int(mask.shape[0])
    fanout = int(tree_entry.meta["fanout"])
    G = int(tree_entry.meta["groups"])
    Pp = int(plane.shape[0])
    if Q == 0 or Pp != G * fanout:
        _note_tree(path="flat_small", groups=G)
        return topk_init_batched_device(plane, mask, k, mode, mesh)
    m = mask
    if m.shape[1] < Pp:
        m = np.pad(m, ((0, 0), (0, Pp - m.shape[1])))
    gunion = m.reshape(Q, G, fanout).any(axis=(0, 2))      # [G]
    dens = gunion.sum() / G
    if dens > dense_cutoff:
        _note_tree(path="flat_dense", groups=G, fine_density=float(dens))
        return topk_init_batched_device(plane, mask, k, mode, mesh)
    gids = np.nonzero(gunion)[0]
    _note_tree(path="tree", groups=G, fine_density=float(dens))
    if not gids.size:
        return np.full((Q, k), -np.inf, dtype=np.float32)
    pos = (gids[:, None] * fanout
           + np.arange(fanout)[None, :]).reshape(-1).astype(np.int32)
    cplane = jnp.take(plane, jnp.asarray(pos), axis=0)
    return topk_init_batched_device(cplane, m[:, pos], k, mode, mesh)


# ---------------------------------------------------------------------------
# Top-k / join staging
# ---------------------------------------------------------------------------

def build_block_topk(
    values: np.ndarray,
    part_bounds: np.ndarray,
    k: int,
    mask: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Per-partition block top-k table [P, k] (desc, -inf padded).

    This is the metadata-sketch the TPU top-k path consumes; masked-out
    rows (filter misses, nulls) are excluded.  Segmented formulation: one
    lexsort by (partition, -value) then a rank-within-partition select —
    O(N log N) total with no Python loop over P.

    part_bounds must be non-decreasing row offsets (they are cumulative
    by construction everywhere in the engine).  NaN values are dropped
    (a NaN in a sketch row would corrupt topk_boundary's comparisons).
    """
    part_bounds = np.asarray(part_bounds)
    if np.any(np.diff(part_bounds) < 0):
        raise ValueError("part_bounds must be non-decreasing row offsets")
    P = len(part_bounds) - 1
    out = np.full((P, k), -np.inf, dtype=np.float32)
    values = np.asarray(values)
    # Clamp like the slice values[s:e] would: bounds may overrun values.
    cb = np.clip(part_bounds, 0, len(values))
    lo_row, hi_row = int(cb[0]), int(cb[-1])
    # Widen, don't round-to-nearest: a plane value must never understate
    # the block's potential, or the boundary test could skip a match.
    vals = round_up_f32(values[lo_row:hi_row])
    pid = np.repeat(np.arange(P), np.diff(cb))
    if mask is not None:
        sel = np.asarray(mask, dtype=bool)[lo_row:hi_row]
        vals = vals[sel]
        pid = pid[sel]
    finite = ~np.isnan(vals)
    if not finite.all():
        vals = vals[finite]
        pid = pid[finite]
    if vals.size == 0:
        return out
    order = np.lexsort((-vals, pid))        # partition-major, value desc
    pid_s = pid[order]
    vals_s = vals[order]
    starts = np.searchsorted(pid_s, np.arange(P), side="left")
    rank = np.arange(len(vals_s)) - starts[pid_s]
    keep = rank < k
    out[pid_s[keep], rank[keep]] = vals_s[keep]
    return out


def topk_boundary_device(
    rows: np.ndarray,
    b_init: float = -np.inf,
    mode: str = "auto",
) -> Tuple[np.ndarray, np.ndarray]:
    """(skip [P], heap [k]) for pre-ordered block top-k rows."""
    rows_j = jnp.asarray(rows, dtype=jnp.float32)
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        skip, heap = ref.topk_boundary_ref(rows_j, b_init)
    elif mode == "prefix":
        skip, heap = ref.topk_boundary_prefix_ref(rows_j, b_init)
    else:
        # round the upfront boundary down so a narrowed b_init can never
        # skip a block the f64 boundary would have kept
        b32 = jnp.asarray(round_down_f32(b_init))
        skip, heap = topk_boundary(rows_j, b32,
                                   interpret=kernel_interpret(mode))
    return np.asarray(skip), np.asarray(heap)


def join_overlap_device(
    stats: PartitionStats,
    key_col: str,
    distinct: np.ndarray,
    mode: str = "auto",
) -> np.ndarray:
    """hit [P] int32: 1 where a build key may live in the partition."""
    pmin = jnp.asarray(round_down_f32(stats.col_min(key_col)))
    pmax = jnp.asarray(round_up_f32(stats.col_max(key_col)))
    d = jnp.asarray(np.asarray(distinct, dtype=np.float32))
    if mode == "ref" or (mode == "auto" and not _on_tpu()):
        hit = ref.join_overlap_ref(pmin, pmax, d)
    else:
        hit = join_overlap(pmin, pmax, d,
                           interpret=kernel_interpret(mode))
    return np.asarray(hit)


# ---------------------------------------------------------------------------
# Batched runtime-technique paths (resident join-key / block-top-k planes)
# ---------------------------------------------------------------------------

def pack_distinct(
    distinct_lists: Sequence[np.ndarray],
) -> np.ndarray:
    """Pack per-query sorted distinct keys into the [Qb, Db] kernel layout.

    Db/Qb are power-of-two buckets (``d_bucket`` / ``q_bucket``); padding
    is +inf — sorted last (the ref path binary-searches each row) and
    never inside a finite range (the kernel path compares directly).
    """
    Q = len(distinct_lists)
    Db = d_bucket(max((len(d) for d in distinct_lists), default=1))
    Qb = q_bucket(Q)
    dist = np.full((Qb, Db), np.inf, dtype=np.float32)
    for qi, d in enumerate(distinct_lists):
        dist[qi, : len(d)] = np.asarray(d, dtype=np.float32)
    return dist


def join_overlap_batched_device(
    distinct_lists: Sequence[np.ndarray],
    pmin: jnp.ndarray,       # [P] resident f32 key-column minima (widened)
    pmax: jnp.ndarray,       # [P] resident f32 key-column maxima (widened)
    mode: str = "auto",
    part_ids_lists: Optional[Sequence[np.ndarray]] = None,
    mesh=None,
) -> np.ndarray:
    """hit [Q, P] int32 — Q build summaries vs the resident key plane.

    Row q equals ``join_overlap_device`` for query q's distinct list; one
    launch covers the whole table group.  The f32 key cast is round-to-
    nearest, which is monotone, so a key inside a partition's true f64
    range is always inside the *widened* resident range — the device path
    can keep extra partitions (degrading pruning) but never prunes a
    partition containing a joinable key.

    ``part_ids_lists`` optionally names the partitions each query will
    actually consult (its current scan set).  The kernel path ignores it —
    the resident plane is evaluated dense, that is the batched design —
    but the no-Pallas fallback restricts its C-speed searchsorted to those
    positions (other entries are 0 and must not be read).
    """
    Q = len(distinct_lists)
    P = int(pmin.shape[0])
    shards = mesh_shards(mesh, P)
    if (shards > 1 and not _use_kernel(mode)
            and q_bucket(Q) * P // shards > _REF_SLAB_ELEMS):
        shards = 1     # keep the C-speed searchsorted fallback below
    _note_shards(shards)
    if shards == 1 and (mode == "ref" or (mode == "auto" and not _on_tpu())):
        # np.asarray of a CPU-backed jax array is a view — the resident
        # plane is not copied.  A key k32 hits [pmin, pmax] iff
        # searchsorted brackets it: identical counts to the jnp oracle.
        pmin_h = np.asarray(pmin)
        pmax_h = np.asarray(pmax)
        hit = np.zeros((Q, P), dtype=np.int32)
        for qi, d in enumerate(distinct_lists):
            d32 = np.asarray(d, dtype=np.float32)
            ids = None if part_ids_lists is None else part_ids_lists[qi]
            lo_q = pmin_h if ids is None else pmin_h[ids]
            hi_q = pmax_h if ids is None else pmax_h[ids]
            lo = np.searchsorted(d32, lo_q, side="left")
            hi = np.searchsorted(d32, hi_q, side="right")
            row = (hi > lo).astype(np.int32)
            if ids is None:
                hit[qi] = row
            else:
                hit[qi, ids] = row
        return hit
    with span("pack", q=Q):
        dist_d = jnp.asarray(pack_distinct(distinct_lists))
    with span("dispatch", q=Q, P=P):
        if shards > 1:
            out = _sharded_join(mesh, *_body_flags(mode))(dist_d, pmin, pmax)
        else:
            out = join_overlap_batched(dist_d, pmin, pmax,
                                       interpret=kernel_interpret(mode))
    hit = _fetch(out)
    with span("unpack", q=Q, P=P):
        return hit[:Q]


def pack_blooms(blooms: Sequence) -> Tuple[np.ndarray, np.ndarray]:
    """Pack Q blocked-Bloom filters into the kernel's [Qb, 16, Bb] layout.

    Returns (lo, hi): exact f32 16-bit halves of the filter words, word
    index on the sublane dim (pre-transposed for the kernel's one-hot
    matmul gather).  Each filter is tiled periodically up to the common
    power-of-two Bb bucket: blocked-Bloom block selection is
    ``h & (n_blocks - 1)``, and ``tiled[h & (Bb - 1)] == words[h & (nb - 1)]``
    for any pow-2 multiple Bb, so every query in a launch shares one
    block mask and recompiles stay bounded by |buckets|.  Query rows
    beyond Q are all-zero filters (never a hit; sliced off).
    """
    Q = len(blooms)
    Bb = bloom_bucket(max(b.n_blocks for b in blooms))
    Qb = q_bucket(Q)
    lo = np.zeros((Qb, BLOCK_WORDS, Bb), dtype=np.float32)
    hi = np.zeros((Qb, BLOCK_WORDS, Bb), dtype=np.float32)
    for qi, b in enumerate(blooms):
        w = b.words.reshape(b.n_blocks, BLOCK_WORDS).T        # [16, nb]
        w = np.tile(w, (1, Bb // b.n_blocks))                 # [16, Bb]
        lo[qi] = (w & np.uint32(0xFFFF)).astype(np.float32)
        hi[qi] = (w >> np.uint32(16)).astype(np.float32)
    return lo, hi


def bloom_probe_batched_device(
    blooms: Sequence,        # Q core.prune_join.BlockedBloom filters
    pmin: jnp.ndarray,       # [P] int32 resident enumeration minima
    width: jnp.ndarray,      # [P] int32 resident candidate counts (0=keep)
    wmax: int,               # host-side max raw width (plane metadata)
    enum_limit: int,
    mode: str = "auto",
    part_ids_lists: Optional[Sequence[np.ndarray]] = None,
    mesh=None,
) -> np.ndarray:
    """hit [Q, P] int32 — Q Bloom summaries vs the resident enumeration
    plane; row q equals the (fixed) host matcher's narrow-range
    enumeration for query q's filter, false-positive-only by construction
    (hit is 0 only where 0 < width <= enum_limit and no candidate value
    is in the filter).

    The no-Pallas fallback exploits narrowness *sparsity*: only
    enumerable partitions — restricted to each query's scan set when
    ``part_ids_lists`` names it (other entries are 1 and must not be
    read) — go through the host BlockedBloom probe at C speed.  The
    kernel path evaluates the resident plane dense (the batched design)
    with a per-partition dynamic trip count.
    """
    Q = len(blooms)
    P = int(pmin.shape[0])
    shards = mesh_shards(mesh, P)
    eb = enum_bucket(max(1, min(int(wmax), int(enum_limit))))
    if (shards > 1 and not _use_kernel(mode)
            and q_bucket(Q) * P * eb // shards > _REF_SLAB_ELEMS):
        # the jnp oracle body is dense O(Q*P*E) — at fleet shapes the
        # sparsity-aware host BlockedBloom fallback below wins (and the
        # dense body could exhaust memory); only the kernel path shards
        # unconditionally
        shards = 1
    _note_shards(shards)
    if shards == 1 and (mode == "ref" or (mode == "auto" and not _on_tpu())):
        # np.asarray of a CPU-backed jax array is a view — no copy.
        pmin_h = np.asarray(pmin)
        width_h = np.asarray(width)
        hit = np.ones((Q, P), dtype=np.int32)
        for qi, bloom in enumerate(blooms):
            ids = (np.arange(P) if part_ids_lists is None
                   else np.asarray(part_ids_lists[qi]))
            w = width_h[ids]
            nids = ids[(w > 0) & (w <= enum_limit)]
            if not nids.size:
                continue
            wq = width_h[nids]
            span_w = int(wq.max())
            cand = (pmin_h[nids][:, None].astype(np.int64)
                    + np.arange(span_w)[None, :])
            valid = np.arange(span_w)[None, :] < wq[:, None]
            hits = bloom.contains(cand.reshape(-1)).reshape(cand.shape)
            hit[qi, nids[~(hits & valid).any(axis=1)]] = 0
        return hit
    with span("pack", q=Q):
        lo, hi = pack_blooms(blooms)
        lo_d, hi_d = jnp.asarray(lo), jnp.asarray(hi)
        width_eff = jnp.where(width <= enum_limit, width, 0).astype(jnp.int32)
    with span("dispatch", q=Q, P=P):
        if shards > 1:
            fn = _sharded_bloom(mesh, *_body_flags(mode), eb)
            out = fn(lo_d, hi_d, pmin, width_eff)
        else:
            out = bloom_probe_batched(lo_d, hi_d, pmin, width_eff, enum_pad=eb,
                                      interpret=kernel_interpret(mode))
    hit = _fetch(out)
    with span("unpack", q=Q, P=P):
        return hit[:Q]


def topk_init_batched_device(
    plane: jnp.ndarray,      # [P, K] resident block-top-k rows (signed f32)
    mask: np.ndarray,        # [Q, P] 1 where partition p is a candidate
    k: int,
    mode: str = "auto",
    mesh=None,
) -> np.ndarray:
    """heap [Q, k] f32 — per-query top-k over masked resident plane rows.

    Query q's Sec. 5.4 upfront boundary for any effective kq <= k is
    ``heap[q, kq - 1]`` (-inf when fewer than kq candidates exist).

    The no-Pallas fallback exploits the masks' sparsity — candidate sets
    (fully-matching partitions of selective queries) are tiny fractions
    of P, so a gather + partition per query beats the kernel's dense
    formulation on CPU (np.asarray of a CPU-backed jax array is a view,
    so the resident plane is not copied).  Top-k is a pure selection, so
    every path returns the identical value multiset per query.
    """
    mask = np.asarray(mask)
    Q = int(mask.shape[0])
    # Delta-staged planes carry sentinel capacity slots past the table's
    # logical P; widen the mask with zeros so shapes line up (the slots
    # are all -inf and masked out — they contribute nothing either way).
    Pp = int(plane.shape[0])
    if mask.shape[1] < Pp:
        mask = np.pad(mask, ((0, 0), (0, Pp - mask.shape[1])))
    shards = mesh_shards(mesh, Pp)
    if (shards > 1 and not _use_kernel(mode)
            and Q * Pp * int(plane.shape[1]) // shards > _REF_SLAB_ELEMS):
        shards = 1     # dense O(Q*P*K) oracle body: the sparse numpy
                       # gather below wins at fleet shapes
    _note_shards(shards)
    if shards == 1 and (mode == "ref" or (mode == "auto" and not _on_tpu())):
        plane_np = np.asarray(plane)
        heap = np.full((Q, k), -np.inf, dtype=np.float32)
        for qi in range(Q):
            ids = np.nonzero(mask[qi])[0]
            if not ids.size:
                continue
            vals = plane_np[ids].ravel()
            vals = vals[vals > -np.inf]
            if not vals.size:
                continue
            if vals.size > k:
                vals = np.partition(vals, vals.size - k)[-k:]
            top = np.sort(vals)[::-1]
            heap[qi, : top.size] = top
        return heap
    with span("pack", q=Q):
        mask_d = jnp.asarray(mask.astype(np.float32))      # [Q, Pp]
    with span("dispatch", q=Q, P=Pp):
        if shards > 1:
            out = _sharded_topk(mesh, *_body_flags(mode), k)(plane, mask_d)
        else:
            out = topk_init_batched(plane, mask_d, k,
                                    interpret=kernel_interpret(mode))
    heap = _fetch(out)
    with span("unpack", q=Q, P=Pp):
        if shards > 1:
            # Rank-selection merge of the per-shard heaps [n, Q, k]: top-k
            # is a pure selection, so selecting k from the union of
            # shard-local top-k heaps is exactly the global top-k (same
            # value multiset).
            allv = np.concatenate(list(heap), axis=1)        # [Q, n*k]
            heap = -np.sort(-allv, axis=1)[:, :k]
    return heap
