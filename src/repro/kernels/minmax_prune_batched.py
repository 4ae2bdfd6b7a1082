"""Pallas TPU kernel: multi-query conjunctive-range pruning, one launch.

The single-query kernel (minmax_prune.py) amortizes nothing across a
workload: Q queries mean Q stagings and Q launches.  This kernel evaluates
**Q queries x Kb constraints x P partitions** in one launch against the
table's *resident* ``[C, P]`` metadata planes (core/device_stats.py), so a
heavy workload's pruning decisions ride a single grid.

Layout (DESIGN.md §2 conventions):
  * queries are packed on the **sublane** dimension (BLOCK_Q = 8, the f32
    tile height); partitions stay on the 128-wide lane dimension;
  * each query brings a ``[Kb]`` row of (cid, lo, hi) constraints.  Kb is
    the query batch's constraint count padded to a power-of-two bucket
    (ops.k_bucket) with ``(-inf, +inf)`` no-op ranges, so jit recompiles
    are bounded by |buckets| x |tables| instead of per-batch shapes;
  * the per-constraint stat row is gathered **in-kernel** from the
    ``[C, BLOCK_P]`` stats tile via a one-hot matmul
    (``onehot(cid) [BQ, C] @ stats [C, BP]``) — an MXU-native gather that
    never materializes a ``[Q, K, P]`` intermediate anywhere.

Per (query, constraint, partition) the three-valued lattice is the same
as minmax_prune.py; no-op padding rows contribute tv=2 (the AND identity).
A padded query row (all no-ops) therefore yields tv=2 and is sliced off.

Wide integer columns (``limbs > 1``; core/device_stats.py precision
contract): each constraint brings ``limbs`` (row, lo, hi) slots, most
significant first, and compares its stats with its bounds
lexicographically over the limbs, every one exact in f32.  A narrow
constraint in such a launch fills its first slot as with ``limbs=1`` and
its others with row -1 (the one-hot gather reads 0) and bounds 0, so its
compare is the plain one.  The first slot carries the no-op padding test
and the demote row.

Block layout per grid step (i over query blocks, j over partition blocks):
  cids/lo/hi:        [BLOCK_Q, Kb * limbs]  (i, 0)
  mins/maxs/demote:  [C, BLOCK_P]   (0, j)   — revisited, stays in VMEM
  tv out:            [BLOCK_Q, BLOCK_P] int32 (i, j)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_Q = 8      # queries per tile: the f32 sublane height
BLOCK_P = 2048   # partitions per tile: C*BLOCK_P*4B*3 stays << VMEM

_NEG = float("-inf")
_POS = float("inf")


def _batched_kernel(cids_ref, lo_ref, hi_ref, mins_ref, maxs_ref, dem_ref,
                    tv_ref, *, limbs: int):
    C = mins_ref.shape[0]
    BQ, KL = lo_ref.shape
    BP = mins_ref.shape[1]
    mins = mins_ref[...]          # [C, BP]
    maxs = maxs_ref[...]
    dem = dem_ref[...]
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (BQ, C), 1)

    tv = jnp.full((BQ, BP), 2, dtype=jnp.int32)
    for k in range(KL // limbs):  # static unroll: Kb is a small power of two
        # Lexicographic compare, least significant limb first: each
        # relation holds where it holds at this limb, or where the limb
        # ties and it held below.  With limbs=1 it is the plain compare.
        lt_xa = gt_mb = lt_ma = gt_xb = gt_mx = None
        for j in reversed(range(k * limbs, (k + 1) * limbs)):
            cid = cids_ref[:, j]                   # [BQ] int32
            onehot = (cid[:, None] == col_iota).astype(jnp.float32)
            # One-hot gather: exactly one 1.0 per row, so the matmul is an
            # exact row select, executed on the MXU — at HIGHEST precision
            # only: the TPU's default single bf16 pass keeps 8 mantissa
            # bits and rounds the stats (measured on a v5e).
            pmin = jnp.dot(onehot, mins, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
            pmax = jnp.dot(onehot, maxs, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
            lo = lo_ref[:, j][:, None]             # [BQ, 1]
            hi = hi_ref[:, j][:, None]
            if lt_xa is None:
                lt_xa, gt_mb = pmax < lo, pmin > hi
                lt_ma, gt_xb, gt_mx = pmin < lo, pmax > hi, pmin > pmax
            else:
                lt_xa = (pmax < lo) | ((pmax == lo) & lt_xa)
                gt_mb = (pmin > hi) | ((pmin == hi) & gt_mb)
                lt_ma = (pmin < lo) | ((pmin == lo) & lt_ma)
                gt_xb = (pmax > hi) | ((pmax == hi) & gt_xb)
                gt_mx = (pmin > pmax) | ((pmin == pmax) & gt_mx)
        # the first slot's row and bounds are left in onehot / lo / hi;
        # the 0/1 demote flags are exact in one pass
        pdem = jnp.dot(onehot, dem, preferred_element_type=jnp.float32)

        empty = gt_mx
        no = lt_xa | gt_mb | empty
        full = ~lt_ma & ~gt_xb & (pdem == 0.0) & ~empty
        tv_k = jnp.where(no, 0, jnp.where(full, 2, 1)).astype(jnp.int32)
        # (-inf, +inf) is the padding sentinel: the AND identity regardless
        # of the gathered stats (extract_ranges never emits it for a real
        # constraint — strict bounds go through nextafter/snapping).
        noop = (lo == _NEG) & (hi == _POS)
        tv_k = jnp.where(noop, 2, tv_k)
        tv = jnp.minimum(tv, tv_k)
    tv_ref[...] = tv


@functools.partial(jax.jit, static_argnames=("interpret", "limbs"))
def minmax_prune_batched(
    cids: jax.Array,      # [Q, Kb * limbs] int32 plane rows (-1: reads 0)
    lo: jax.Array,        # [Q, Kb * limbs] f32 range lows  (inclusive; -inf pad)
    hi: jax.Array,        # [Q, Kb * limbs] f32 range highs (inclusive; +inf pad)
    mins: jax.Array,      # [C, P] f32 resident partition minima (widened)
    maxs: jax.Array,      # [C, P] f32 resident partition maxima (widened)
    demote: jax.Array,    # [C, P] f32 1.0 where FULL must be suppressed
    interpret: bool = False,
    limbs: int = 1,       # slots per constraint (wide columns: 2 or 3)
) -> jax.Array:
    """Returns tv [Q, P] int32 in {0, 1, 2}.

    mins/maxs must be FINITE (core.device_stats.cast_stats_f32 clamps
    ±inf to ±f32max): the one-hot matmul gather multiplies every stat by
    0 or 1, and 0 x inf = NaN would silently corrupt the lattice.
    """
    Q, Kb = lo.shape
    C, P = mins.shape

    pad_q = (-Q) % BLOCK_Q
    if pad_q:
        # Padded queries are all no-op constraints -> tv 2; sliced off.
        cids = jnp.pad(cids, ((0, pad_q), (0, 0)))
        lo = jnp.pad(lo, ((0, pad_q), (0, 0)), constant_values=_NEG)
        hi = jnp.pad(hi, ((0, pad_q), (0, 0)), constant_values=_POS)
    pad_p = (-P) % BLOCK_P
    if pad_p:
        # Padded partitions get an empty interval -> tv 0; sliced off.
        # Finite extremes, not ±inf: a 0-weight x inf product in the
        # one-hot gather matmul would poison gathered rows with NaN —
        # core.device_stats clamps the real planes for the same reason.
        fmax = float(jnp.finfo(jnp.float32).max)
        mins = jnp.pad(mins, ((0, 0), (0, pad_p)), constant_values=fmax)
        maxs = jnp.pad(maxs, ((0, 0), (0, pad_p)), constant_values=-fmax)
        demote = jnp.pad(demote, ((0, 0), (0, pad_p)))
    Qp, Pp = Q + pad_q, P + pad_p

    grid = (Qp // BLOCK_Q, Pp // BLOCK_P)
    tv = pl.pallas_call(
        functools.partial(_batched_kernel, limbs=limbs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_Q, Kb), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_Q, Kb), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_Q, Kb), lambda i, j: (i, 0)),
            pl.BlockSpec((C, BLOCK_P), lambda i, j: (0, j)),
            pl.BlockSpec((C, BLOCK_P), lambda i, j: (0, j)),
            pl.BlockSpec((C, BLOCK_P), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_Q, BLOCK_P), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Pp), jnp.int32),
        interpret=interpret,
        name="minmax_prune_batched",
    )(cids, lo, hi, mins, maxs, demote)
    return tv[:Q, :P]
