"""Pure-jnp oracles for the Pallas kernels (no pallas imports).

Each function implements the identical contract with straightforward
jax.numpy, serving as the allclose reference in tests and as the
fallback implementation on backends without Pallas.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def minmax_prune_ref(lo, hi, mins, maxs, nullable) -> jax.Array:
    """tv [P] int32 for a conjunction of K ranges over [K, P] stats."""
    lo = lo[:, None]
    hi = hi[:, None]
    empty = mins > maxs
    no = (maxs < lo) | (mins > hi) | empty
    full = (mins >= lo) & (maxs <= hi) & (nullable == 0.0) & ~empty
    tv_k = jnp.where(no, 0, jnp.where(full, 2, 1)).astype(jnp.int32)
    return jnp.min(tv_k, axis=0)


def _relations(rows, lo, hi):
    """One constraint's five stat-vs-bound relations (pmax < lo,
    pmin > hi, pmin < lo, pmax > hi, pmin > pmax) from its gathered
    (pmin, pmax) limb pairs, most significant first, and its [Q, 1] limb
    bounds: the lexicographic compare of kernels/minmax_prune_batched.py,
    least significant limb first (the plain compare with one limb)."""
    lt_xa = gt_mb = lt_ma = gt_xb = gt_mx = None
    for i in reversed(range(len(rows))):
        pmin, pmax = rows[i]
        a, b = lo[i], hi[i]
        if lt_xa is None:
            lt_xa, gt_mb = pmax < a, pmin > b
            lt_ma, gt_xb, gt_mx = pmin < a, pmax > b, pmin > pmax
        else:
            lt_xa = (pmax < a) | ((pmax == a) & lt_xa)
            gt_mb = (pmin > b) | ((pmin == b) & gt_mb)
            lt_ma = (pmin < a) | ((pmin == a) & lt_ma)
            gt_xb = (pmax > b) | ((pmax == b) & gt_xb)
            gt_mx = (pmin > pmax) | ((pmin == pmax) & gt_mx)
    return lt_xa, gt_mb, lt_ma, gt_xb, gt_mx


def _combine(tv, rel, pdem, lo0, hi0):
    """AND one constraint's tri-valued verdict into ``tv``; the first
    limb's bounds carry the ``(-inf, +inf)`` padding no-op."""
    lt_xa, gt_mb, lt_ma, gt_xb, empty = rel
    no = lt_xa | gt_mb | empty
    full = ~lt_ma & ~gt_xb & (pdem == 0.0) & ~empty
    tv_k = jnp.where(no, 0, jnp.where(full, 2, 1)).astype(jnp.int32)
    noop = (lo0 == -jnp.inf) & (hi0 == jnp.inf)
    return jnp.minimum(tv, jnp.where(noop, 2, tv_k))


def minmax_prune_batched_ref(cids, lo, hi, mins, maxs, demote,
                             limbs: int = 1) -> jax.Array:
    """tv [Q, P] int32 for Q queries of Kb ranges over resident [C, P] stats.

    Mirrors kernels/minmax_prune_batched.py: per-constraint stat rows are
    gathered from the resident planes by row id; ``(-inf, +inf)``
    constraints are padding no-ops (tv=2, the AND identity).  With
    ``limbs > 1`` each constraint is ``limbs`` consecutive (row, lo, hi)
    slots compared lexicographically, row -1 reading 0.  The K loop is a
    static Python unroll so peak memory stays O(Q*P), never O(Q*K*P).
    """
    Q, KL = lo.shape
    P = mins.shape[1]

    def take(plane, c):
        if limbs == 1:
            return jnp.take(plane, c, axis=0)           # [Q, P]
        got = jnp.take(plane, jnp.maximum(c, 0), axis=0)
        return jnp.where((c >= 0)[:, None], got, 0.0)

    tv = jnp.full((Q, P), 2, dtype=jnp.int32)
    for k in range(KL // limbs):
        js = range(k * limbs, (k + 1) * limbs)
        rows = [(take(mins, cids[:, j]), take(maxs, cids[:, j])) for j in js]
        rel = _relations(rows, [lo[:, j][:, None] for j in js],
                         [hi[:, j][:, None] for j in js])
        j0 = k * limbs
        tv = _combine(tv, rel, take(demote, cids[:, j0]),
                      lo[:, j0][:, None], hi[:, j0][:, None])
    return tv


def minmax_prune_gathered_ref(cids, lo, hi, mins, maxs, demote, pos,
                              limbs: int = 1) -> jax.Array:
    """tv [Q, W] int32 over per-query *gathered* plane positions.

    The tree path's survivor-restricted evaluator: column w of row q is
    plane position ``pos[q, w]`` (an index into the flattened partition
    dim — used both for the fine group planes and the leaf planes), so
    entry (q, w) equals ``minmax_prune_batched_ref(...)[q, pos[q, w]]``
    bit-for-bit — the gather commutes with every elementwise step of the
    tri-valued conjunction.  Duplicate or padding positions simply
    recompute the same truthful verdict.  ``limbs`` as in
    ``minmax_prune_batched_ref``.
    """
    Q, KL = lo.shape
    stride = mins.shape[1]
    fm = mins.reshape(-1)
    fx = maxs.reshape(-1)
    fd = demote.reshape(-1)

    def take(flat, c):
        if limbs == 1:
            return jnp.take(flat, c[:, None] * stride + pos)   # [Q, W]
        got = jnp.take(flat, jnp.maximum(c, 0)[:, None] * stride + pos)
        return jnp.where((c >= 0)[:, None], got, 0.0)

    tv = jnp.full(pos.shape, 2, dtype=jnp.int32)
    for k in range(KL // limbs):
        js = range(k * limbs, (k + 1) * limbs)
        rows = [(take(fm, cids[:, j]), take(fx, cids[:, j])) for j in js]
        rel = _relations(rows, [lo[:, j][:, None] for j in js],
                         [hi[:, j][:, None] for j in js])
        j0 = k * limbs
        tv = _combine(tv, rel, take(fd, cids[:, j0]),
                      lo[:, j0][:, None], hi[:, j0][:, None])
    return tv


def topk_boundary_ref(rows: jax.Array, b_init) -> tuple:
    """(skip [P] int32, heap [k]) — sequential lax.scan with jnp.sort."""
    P, k = rows.shape
    b_init = jnp.asarray(b_init, rows.dtype)

    def step(heap, row):
        h_kth = heap[k - 1]
        heap_full = h_kth > -jnp.inf
        bm = row[0]
        eff = jnp.maximum(b_init, jnp.where(heap_full, h_kth, -jnp.inf))
        skip = (bm < eff) | (heap_full & (bm <= h_kth))
        merged = jnp.sort(jnp.concatenate([heap, row]))[::-1][:k]
        heap = jnp.where(skip, heap, merged)
        return heap, skip.astype(jnp.int32)

    heap0 = jnp.full((k,), -jnp.inf, rows.dtype)
    heap, skips = jax.lax.scan(step, heap0, rows)
    return skips, heap


def topk_boundary_prefix_ref(rows: jax.Array, b_init) -> tuple:
    """DESIGN.md §6: the *associative prefix-merge* formulation.

    top-k-merge is associative, so the evolving heap is an exclusive
    prefix-scan over block top-k rows — parallelizable in log depth with
    jax.lax.associative_scan, unlike the sequential heap.  Because the
    prefix heap merges every row (including ones the sequential algorithm
    skipped — all of which sit at or below the running k-th value), its
    k-th value is always >= the sequential heap's.  Consequences (tested):
      * the final top-k value multiset is IDENTICAL, and
      * the skip mask is a SUPERSET of the sequential one — the parallel
        formulation prunes at least as much.  A beyond-paper improvement.
    """
    P, k = rows.shape
    b_init = jnp.asarray(b_init, rows.dtype)

    def merge(a, b):
        return jnp.sort(jnp.concatenate([a, b], axis=-1), axis=-1)[..., ::-1][..., :k]

    inclusive = jax.lax.associative_scan(merge, rows, axis=0)      # [P, k]
    prev = jnp.concatenate(
        [jnp.full((1, k), -jnp.inf, rows.dtype), inclusive[:-1]], axis=0
    )
    h_kth = prev[:, k - 1]
    heap_full = h_kth > -jnp.inf
    bm = rows[:, 0]
    eff = jnp.maximum(b_init, jnp.where(heap_full, h_kth, -jnp.inf))
    skip = (bm < eff) | (heap_full & (bm <= h_kth))
    return skip.astype(jnp.int32), inclusive[-1]


# ---------------------------------------------------------------------------
# Blocked-Bloom probe primitives (shared by the oracle and the Pallas kernel)
# ---------------------------------------------------------------------------

# Murmur3 finalizer constants as int32 bit patterns (the host mixer in
# core.prune_join works in uint32; two's-complement wraparound is the same
# mod-2^32 arithmetic, so int32 lanes produce identical bits).
MURMUR_C1 = 0x85EBCA6B - (1 << 32)
MURMUR_C2 = 0xC2B2AE35 - (1 << 32)
H1_SALT = 0x9E3779B9 - (1 << 32)
H2_SALT = 0x7F4A7C15


def lsr32(x: jax.Array, s: int) -> jax.Array:
    """Logical right shift of int32 lanes by a constant: the arithmetic
    shift's sign fill is masked off (TPU has no unsigned shift)."""
    if s == 0:
        return x
    return (x >> s) & jnp.int32((1 << (32 - s)) - 1)


def mix32(x: jax.Array) -> jax.Array:
    """Murmur3 finalizer on int32 lanes — bit-identical to the uint32
    host mixer ``core.prune_join._mix32``."""
    x = x ^ lsr32(x, 16)
    x = x * jnp.int32(MURMUR_C1)
    x = x ^ lsr32(x, 13)
    x = x * jnp.int32(MURMUR_C2)
    x = x ^ lsr32(x, 16)
    return x


def bloom_probe_batched_ref(lo_t, hi_t, pmin, width, enum_pad: int) -> jax.Array:
    """hit [Q, P] int32 — jnp oracle for kernels/bloom_probe.py.

    ``lo_t``/``hi_t`` are the packed filters (ops.pack_blooms): [Q, 16, Bb]
    f32 halves of each query's filter words, tiled to the common Bb block
    bucket.  ``pmin``/``width`` are the int32 enumeration rows (width 0 =
    not enumerable = keep).  Dense gather formulation — peak memory is
    O(Q*P*E), so this is the small-shape test oracle; the production
    no-Pallas fallback (ops.bloom_probe_batched_device) instead exploits
    narrowness sparsity with the host BlockedBloom probe.
    """
    Q, _w16, Bb = lo_t.shape
    words = (hi_t.astype(jnp.int32) << 16) | lo_t.astype(jnp.int32)
    flat = words.reshape(Q, -1)                        # [Q, 16 * Bb]
    pmin = pmin.astype(jnp.int32)
    width = width.astype(jnp.int32)
    j = jnp.arange(enum_pad, dtype=jnp.int32)
    cand = pmin[:, None] + j[None, :]                  # [P, E]
    h0 = mix32(cand ^ mix32(cand >> 31))               # >> 31: int64 hi word
    h1 = mix32(h0 ^ jnp.int32(H1_SALT))
    h2 = mix32(h1 ^ jnp.int32(H2_SALT))
    block = h0 & jnp.int32(Bb - 1)
    ok = jnp.ones((Q,) + cand.shape, dtype=bool)
    for i in range(4):
        wi = lsr32(h1, 8 * i) & 15
        bi = lsr32(h2, 8 * i) & 31
        idx = wi * Bb + block                          # [P, E] word index
        w = jnp.take(flat, idx.reshape(-1), axis=1).reshape(ok.shape)
        ok &= ((w >> bi[None]) & 1) == 1
    valid = j[None, :] < width[:, None]                # [P, E]
    hit = jnp.any(ok & valid[None], axis=2) | (width == 0)[None, :]
    return hit.astype(jnp.int32)


def join_overlap_ref(pmin, pmax, distinct) -> jax.Array:
    """hit [P] int32 via searchsorted (the CPU engine's formulation)."""
    lo = jnp.searchsorted(distinct, pmin, side="left")
    hi = jnp.searchsorted(distinct, pmax, side="right")
    return (hi > lo).astype(jnp.int32)


def join_overlap_batched_ref(dist, pmin, pmax) -> jax.Array:
    """hit [Q, P] int32 for Q queries' distinct lists vs one key plane.

    Mirrors kernels/join_overlap.py::join_overlap_batched: ``dist`` is
    [Q, Db] with row q holding query q's *sorted* distinct keys, padded
    with +inf — which sorts last and, with the plane clamped to finite
    f32 (pmax <= f32max), can never land inside a range, so the
    searchsorted counts are untouched by padding."""
    def one(d):
        lo = jnp.searchsorted(d, pmin, side="left")
        hi = jnp.searchsorted(d, pmax, side="right")
        return (hi > lo).astype(jnp.int32)

    return jax.vmap(one)(dist)


def topk_init_batched_ref(plane, mask, k: int) -> jax.Array:
    """heap [Q, k] — dense masked broadcast + lax.top_k.

    Mirrors kernels/topk_boundary.py::topk_init_batched; peak memory is
    O(Q*P*K), so it serves as the small-shape test oracle.  The
    production no-Pallas fallback (ops.topk_init_batched_device) instead
    exploits mask sparsity with a per-query numpy gather + partition —
    top-k is a pure selection, so both return identical values.
    ``mask`` is [Q, P], like the kernel's."""
    Q = mask.shape[0]
    vals = jnp.where(mask[:, :, None] > 0, plane[None, :, :], -jnp.inf)
    flat = vals.reshape(Q, -1)
    if flat.shape[1] < k:
        flat = jnp.pad(flat, ((0, 0), (0, k - flat.shape[1])),
                       constant_values=-jnp.inf)
    return jax.lax.top_k(flat, k)[0]
