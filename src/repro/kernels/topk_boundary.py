"""Pallas TPU kernel: top-k boundary-value scan (paper Sec. 5).

The WAND-style runtime pruning loop as a TPU kernel.  Input is the
per-partition *block top-k table*: ``rows[P, k]`` where row p holds
partition p's k largest (signed) order-column values sorted descending,
padded with -inf (rows are pre-arranged in processing order — Sec. 5.3 —
and pre-masked by the scan's filter predicate).  The kernel walks the
partitions sequentially, carrying the global top-k heap, and emits

  * ``skip[P]``  — 1 where the partition would be pruned by the boundary
                   (these partitions would never be fetched from storage),
  * ``heap[k]``  — the final top-k values.

Skip rule (proved in core/prune_topk.py and hypothesis-tested):
  with B = upfront boundary (Sec. 5.4) and H = current heap k-th value,
  skip iff  block_max < max(B, H)  or  (heap full and block_max <= H).

TPU mapping: the heap/row merge is *rank-selection* — an all-pairs
comparison of the 2k candidates followed by a one-hot combine — which is
branch-free VPU work (2k <= 256 lanes), instead of the CPU heap's
branchy sift-down.  The partition dimension is blocked (BLOCK_ROWS rows
per grid step) with the heap carried across grid steps in VMEM scratch.
The sequential carry is the paper's semantics; a fully parallel
formulation (associative prefix merge) is discussed in DESIGN.md §6 and
validated in the ref oracle.

Values must be finite (the wrapper uses -inf as padding / null encoding).

``topk_init_batched`` is the workload-scale boundary *initializer* (Sec.
5.4): against the table's resident block-top-k plane (core/device_stats.py
— [P, K] per-partition top-K rows, staged once per table version), one
launch computes every query's upfront boundary as the k-th largest value
over its fully-matching partitions' resident rows.  No per-query staging:
only the [Q, P] candidate masks cross to the device per batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256


def _merge_topk(heap: jax.Array, row: jax.Array, k: int) -> jax.Array:
    """Top-k of two descending-sorted length-k vectors via rank selection."""
    cand = jnp.concatenate([heap, row])                     # [2k]
    n = 2 * k
    ci = cand[:, None]                                      # value of i
    cj = cand[None, :]                                      # value of j
    ii = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    rank = jnp.sum((cj > ci) | ((cj == ci) & (jj < ii)), axis=1)  # [2k]
    tgt = jax.lax.broadcasted_iota(jnp.int32, (k, n), 0)
    sel = (rank[None, :] == tgt).astype(cand.dtype)         # one-hot [k, 2k]
    return jnp.sum(sel * cand[None, :], axis=1)             # [k]


def _topk_boundary_kernel(binit_ref, rows_ref, skip_ref, heap_ref, scratch):
    k = rows_ref.shape[1]
    bp = rows_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        scratch[...] = jnp.full_like(scratch, -jnp.inf)

    b_init = binit_ref[0, 0]
    heap0 = scratch[0, :]

    def body(j, carry):
        heap, skips = carry
        row = rows_ref[j, :]
        h_kth = heap[k - 1]
        heap_full = h_kth > -jnp.inf
        bm = row[0]
        eff = jnp.maximum(b_init, jnp.where(heap_full, h_kth, -jnp.inf))
        skip = (bm < eff) | (heap_full & (bm <= h_kth))
        merged = _merge_topk(heap, row, k)
        heap = jnp.where(skip, heap, merged)
        skips = skips.at[j].set(skip.astype(jnp.int32))
        return heap, skips

    heap, skips = jax.lax.fori_loop(
        0, bp, body, (heap0, jnp.zeros((bp,), jnp.int32))
    )
    scratch[0, :] = heap
    skip_ref[...] = skips[None, :]
    heap_ref[...] = heap[None, :]


BLOCK_QI = 8     # queries per tile in the batched init kernel
BLOCK_PI = 512   # partitions folded into the heaps per grid step


def _merge_sorted_rows(heap: jax.Array, rows: jax.Array, k: int) -> jax.Array:
    """Row-wise top-k merge: heap [BQ, k] desc + rows [BQ, m] desc -> [BQ, k].

    Both inputs are sorted, so a stable merge's ranks come from one
    [BQ, k, m] comparison: heap[i] lands at i + #(rows beating it), and
    row[j] at j + #(heap entries at least as large) — ties keep the heap
    entry first, so the ranks are a permutation of 0..k+m-1.  Output slot
    r then selects the one candidate of rank r.  Branch-free VPU work,
    with O(k * (k + m)) intermediates instead of an all-pairs rank over
    the k + m candidates."""
    m = rows.shape[1]
    beats = rows[:, None, :] > heap[:, :, None]              # [BQ, k, m]
    rank_h = (jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
              + jnp.sum(beats.astype(jnp.int32), axis=2))    # [BQ, k]
    rank_r = (jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
              + jnp.sum((~beats).astype(jnp.int32), axis=1))  # [BQ, m]
    tgt = jax.lax.broadcasted_iota(jnp.int32, (1, k, 1), 1)
    # where, not onehot * value: candidates are -inf-padded and
    # 0 * -inf = NaN.  Slot r < k has exactly one owner across the two
    # selections, so the other contributes an exact 0.
    from_h = jnp.sum(jnp.where(rank_h[:, None, :] == tgt,
                               heap[:, None, :], 0.0), axis=2)
    from_r = jnp.sum(jnp.where(rank_r[:, None, :] == tgt,
                               rows[:, None, :], 0.0), axis=2)
    return from_h + from_r                                   # [BQ, k]


def _topk_init_kernel(plane_ref, mask_ref, heap_ref, scratch, *, k):
    BP, K = plane_ref.shape
    kk = min(K, k)              # rows are sorted: only their top k can land
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BP), 1)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        scratch[...] = jnp.full_like(scratch, -jnp.inf)

    mask = mask_ref[...]                                     # [BQ, BP]

    # Most tiles hold no candidate of any of the tile's queries (the
    # masks are selective queries' FULL partitions): skip them whole.
    @pl.when(jnp.any(mask > 0))
    def _fold():
        def body(j, heap):
            # column j of the mask as [BQ, 1]: a masked lane reduction,
            # since Mosaic has no dynamic lane-dim indexing
            m = jnp.max(jnp.where(lane == j, mask, 0.0), axis=1,
                        keepdims=True)

            def merge(h):
                prow = plane_ref[pl.ds(j, 1), :][:, :kk]     # [1, kk]
                rows = jnp.where(m > 0, prow, -jnp.inf)      # [BQ, kk]
                return _merge_sorted_rows(h, rows, k)

            return jax.lax.cond(jnp.any(m > 0), merge, lambda h: h, heap)

        scratch[...] = jax.lax.fori_loop(0, BP, body, scratch[...])

    heap_ref[...] = scratch[...]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_init_batched(
    plane: jax.Array,     # [P, K] f32 resident block-top-k rows, each row
                          #        sorted descending, -inf padded
    mask: jax.Array,      # [Q, P] f32, 1.0 = candidate partition for query q
    k: int,
    interpret: bool = False,
) -> jax.Array:
    """Per-query top-k over masked unions of resident block-top-k rows.

    Returns heap [Q, k] f32 descending (-inf padded): row q holds the k
    largest plane values among partitions with ``mask[q, p] == 1`` — the
    Sec. 5.4 upfront boundary for query q is ``heap[q, kq - 1]`` for any
    kq <= k (a prefix of a larger heap is the exact smaller-k answer, so
    one launch serves a whole group of queries with mixed k).

    The partition dimension is blocked with the heaps carried across grid
    steps in VMEM scratch, like ``topk_boundary``; queries ride the
    sublane dim and partitions the lanes, like ``minmax_prune_batched``.
    """
    P, K = plane.shape
    Q = mask.shape[0]
    pad_q = (-Q) % BLOCK_QI
    if pad_q:
        mask = jnp.pad(mask, ((0, pad_q), (0, 0)))
    pad_p = (-P) % BLOCK_PI
    if pad_p:
        plane = jnp.pad(plane, ((0, pad_p), (0, 0)), constant_values=-jnp.inf)
        mask = jnp.pad(mask, ((0, 0), (0, pad_p)))
    Qp, Pp = Q + pad_q, P + pad_p
    grid = (Qp // BLOCK_QI, Pp // BLOCK_PI)
    heap = pl.pallas_call(
        functools.partial(_topk_init_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_PI, K), lambda i, j: (j, 0)),
            pl.BlockSpec((BLOCK_QI, BLOCK_PI), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_QI, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Qp, k), plane.dtype),
        scratch_shapes=[pltpu.VMEM((BLOCK_QI, k), plane.dtype)],
        interpret=interpret,
    )(plane, mask)
    return heap[:Q]


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_boundary(
    rows: jax.Array,      # [P, k] f32, desc-sorted rows, -inf padded
    b_init: jax.Array,    # scalar f32 upfront boundary (-inf if none)
    interpret: bool = False,
):
    """Returns (skip [P] int32, heap [k] f32)."""
    P, k = rows.shape
    pad = (-P) % BLOCK_ROWS
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)), constant_values=-jnp.inf)
    Pp = P + pad
    grid = (Pp // BLOCK_ROWS,)
    skip, heap = pl.pallas_call(
        _topk_boundary_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_ROWS, k), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_ROWS), lambda i: (0, i)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Pp), jnp.int32),
            jax.ShapeDtypeStruct((1, k), rows.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, k), rows.dtype)],
        interpret=interpret,
    )(jnp.asarray(b_init, rows.dtype).reshape(1, 1), rows)
    # padding rows can never un-skip; slice them off
    return skip[0, :P], heap[0]
