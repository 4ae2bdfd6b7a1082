"""Pallas TPU kernel: build-side distinct keys vs probe partition ranges.

The exact path of JOIN pruning (paper Sec. 6): given the build side's
sorted distinct join keys and every probe partition's [min, max] key
range, decide per partition whether ANY build key falls inside its range
— partitions with no hit are pruned before they are fetched.

TPU adaptation: a CPU engine binary-searches each partition's bounds in
the distinct list (branchy, gather-heavy).  Here it becomes an all-pairs
compare ``[BLOCK_P, BLOCK_D]`` with an any-reduction — dense, branch-free
VPU work with perfect locality: distinct-key blocks stream through VMEM
while the partition block's accumulator is revisited (grid is
(P_blocks, D_blocks) with accumulation over the inner D dimension).

Pad value for the distinct list is NaN: NaN compares false against every
bound, so padding never produces a hit.

``join_overlap_batched`` is the workload-scale variant: Q queries' distinct
lists (packed into power-of-two buckets, +inf padded) against the table's
*resident* join-key plane (core/device_stats.py) in one launch — queries on
the sublane dim like minmax_prune_batched and each query's keys on the
lanes, so a table group's JOIN pruning costs one launch regardless of the
number of queries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_P = 1024
BLOCK_D = 2048
BLOCK_QB = 8     # queries per tile in the batched kernel (f32 sublane height)
LANES = 128      # lane width: the batched kernel walks keys a lane chunk at a time


def _join_overlap_kernel(pmin_ref, pmax_ref, dist_ref, hit_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hit_ref[...] = jnp.zeros_like(hit_ref)

    pmin = pmin_ref[0, :]          # [BP]
    pmax = pmax_ref[0, :]          # [BP]
    d = dist_ref[0, :]             # [BD]
    inside = (d[None, :] >= pmin[:, None]) & (d[None, :] <= pmax[:, None])
    hit_ref[...] |= jnp.any(inside, axis=1).astype(jnp.int32)[None, :]


def _join_overlap_batched_kernel(dist_ref, pmin_ref, pmax_ref, hit_ref):
    BQ, Db = dist_ref.shape
    pmin = pmin_ref[...]           # [1, BP]
    pmax = pmax_ref[...]           # [1, BP]
    width = min(Db, LANES)

    def fold(keys, hit):           # keys [BQ, width]: one lane per build key
        # Static lane slices: Mosaic has no dynamic lane-dim indexing, so
        # keys are walked a 128-lane chunk at a time, unrolled within it.
        for i in range(width):
            dk = keys[:, i:i + 1]                          # [BQ, 1]
            hit = hit | ((dk >= pmin) & (dk <= pmax)).astype(jnp.int32)
        return hit

    hit = jnp.zeros((BQ, pmin.shape[1]), jnp.int32)
    if Db <= LANES:
        hit = fold(dist_ref[...], hit)
    else:
        def body(c, hit):
            off = pl.multiple_of(c * LANES, LANES)
            return fold(dist_ref[:, pl.ds(off, LANES)], hit)

        hit = jax.lax.fori_loop(0, Db // LANES, body, hit)
    hit_ref[...] = hit


@functools.partial(jax.jit, static_argnames=("interpret",))
def join_overlap_batched(
    dist: jax.Array,     # [Q, Db] f32 distinct build keys per query,
                         #         +inf padded (keys on the lane dim)
    pmin: jax.Array,     # [P] f32 resident probe key-column minima (widened,
                         #         FINITE — core.device_stats clamps ±inf)
    pmax: jax.Array,     # [P] f32 resident probe key-column maxima (widened)
    interpret: bool = False,
) -> jax.Array:
    """Batched JOIN overlap: Q build summaries x P probe partitions.

    One launch answers every query of a table group against the resident
    join-key plane — the multi-query analogue of ``join_overlap``, with
    distinct keys packed into power-of-two Db buckets (ops.d_bucket, like
    the K-bucket scheme of minmax_prune_batched) so jit recompiles stay
    bounded.  Padding is ``+inf``: with the plane clamped to finite f32,
    ``+inf <= pmax`` is always False, so a pad key never produces a hit
    (and an all-pad query row yields an all-zero hit row, sliced off).

    Layout: queries on the sublane dim (BLOCK_QB per tile), keys on the
    lanes of the same tile — the key block spans the full Db dim, and Db
    is a power of two, so a block is either the whole (< 128-lane) array
    or whole 128-lane chunks, the only shapes Mosaic tiles.

    Returns hit [Q, P] int32 (0 -> partition is prunable for that query).
    """
    Q, Db = dist.shape
    P = pmin.shape[0]
    if Db > LANES and Db % LANES:
        raise ValueError(f"Db={Db} must be <= {LANES} or a multiple of it")
    pad_q = (-Q) % BLOCK_QB
    if pad_q:
        dist = jnp.pad(dist, ((0, pad_q), (0, 0)), constant_values=jnp.inf)
    pad_p = (-P) % BLOCK_P
    if pad_p:
        # Empty finite intervals, like minmax_prune_batched's P padding.
        fmax = float(jnp.finfo(jnp.float32).max)
        pmin = jnp.pad(pmin, (0, pad_p), constant_values=fmax)
        pmax = jnp.pad(pmax, (0, pad_p), constant_values=-fmax)
    Qp, Pp = Q + pad_q, P + pad_p
    grid = (Qp // BLOCK_QB, Pp // BLOCK_P)
    hit = pl.pallas_call(
        _join_overlap_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_QB, Db), lambda i, j: (i, 0)),
            pl.BlockSpec((1, BLOCK_P), lambda i, j: (0, j)),
            pl.BlockSpec((1, BLOCK_P), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_QB, BLOCK_P), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Pp), jnp.int32),
        interpret=interpret,
        name="join_overlap_batched",
    )(dist, pmin[None, :], pmax[None, :])
    return hit[:Q, :P]


@functools.partial(jax.jit, static_argnames=("interpret",))
def join_overlap(
    pmin: jax.Array,     # [P] f32 probe partition minima of the key column
    pmax: jax.Array,     # [P] f32 probe partition maxima
    distinct: jax.Array, # [D] f32 sorted distinct build keys
    interpret: bool = False,
) -> jax.Array:
    """Returns hit [P] int32 (0 -> partition is prunable)."""
    P = pmin.shape[0]
    D = distinct.shape[0]
    pad_p = (-P) % BLOCK_P
    pad_d = (-D) % BLOCK_D
    if pad_p:
        pmin = jnp.pad(pmin, (0, pad_p), constant_values=jnp.inf)
        pmax = jnp.pad(pmax, (0, pad_p), constant_values=-jnp.inf)
    if pad_d:
        distinct = jnp.pad(distinct, (0, pad_d), constant_values=jnp.nan)
    Pp, Dp = P + pad_p, D + pad_d
    grid = (Pp // BLOCK_P, Dp // BLOCK_D)
    hit = pl.pallas_call(
        _join_overlap_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BLOCK_P), lambda i, j: (0, i)),
            pl.BlockSpec((1, BLOCK_P), lambda i, j: (0, i)),
            pl.BlockSpec((1, BLOCK_D), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_P), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Pp), jnp.int32),
        interpret=interpret,
    )(pmin[None, :], pmax[None, :], distinct[None, :])
    return hit[0, :P]
