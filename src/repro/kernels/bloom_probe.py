"""Pallas TPU kernel: batched blocked-Bloom JOIN pruning (paper Sec. 6).

Large-NDV build sides ship a blocked Bloom filter instead of an exact
distinct set; the probe side then prunes *narrow* partitions — ranges
spanning at most ``enum_limit`` integer/dictionary-code values — by
enumerating every possible value against the filter.  PR 2 left this half
of JOIN pruning on the host; this kernel closes it: **Q Bloom filters x P
probe partitions in one launch** against the table's resident enumeration
plane (core/device_stats.py — integer-snapped pmin/width int32 rows).

TPU adaptation (everything branch-free int32 lane work):

  * the murmur probe pipeline is the shared 32-bit mixer (``ref.mix32`` ==
    ``core.prune_join._mix32`` bit-for-bit; logical shifts emulated by
    masking the arithmetic shift's sign fill);
  * enumeration is vectorized over an ``enum_pad``-wide **lane dim**: one
    [1, E] iota row enumerates a partition's candidate values, hashes
    them, and tests all of them against the filter at once (E is the
    power-of-two bucket of the batch's max width, so recompiles stay
    bounded);
  * the per-candidate 16-word Bloom block is fetched with the engine's
    one-hot **matmul gather** ([BLOCK_QF * 16, Bb] words of a cell's
    filters @ [Bb, E] one-hot — MXU work, no dynamic addressing; the
    candidates' hashes are shared by every filter of the cell).  Word
    values don't fit f32, so filters are packed as exact 16-bit f32
    halves and reassembled in int32;
  * each candidate's 4 probe bits are folded into a per-word *required
    signature* [16, E]; membership is ``(word & sig) == sig`` over the 16
    words — same-word probe collisions OR together exactly like the host;
  * filters are padded to power-of-two block-count buckets by *periodic
    tiling* (``ops.pack_blooms``): block selection is ``h & (blocks-1)``,
    so a tiled filter probes identical words under the larger mask and
    every query in a launch shares one block count.

Partitions ride the grid (BLOCK_PB per cell) with a sequential fori per
partition; non-enumerable partitions (width 0: too wide, float-snapped
empty, or outside int32) short-circuit to hit=1 — skip = keep, so the
kernel is false-positive-only by construction, like the host matcher it
must match bit-for-bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.prune_join import BLOCK_WORDS, K_PROBES
from .ref import H1_SALT, H2_SALT, lsr32, mix32

BLOCK_PB = 128   # partitions per grid cell (sequential fori within)
BLOCK_QF = 8     # filters per grid cell (the f32 sublane height)
# The HIGHEST-precision gather splits both operands into bf16 pieces: at
# the largest bucket (Bb = E = 1024) the cell needs ~20 MiB of VMEM, past
# the 16 MiB scoped default (a v5e core has 128 MiB).
VMEM_LIMIT = 32 * 1024 * 1024


def _bloom_probe_kernel(pmin_ref, width_ref, lo_ref, hi_ref, hit_ref, *,
                        enum_pad):
    BQ, BP = hit_ref.shape
    Bb = lo_ref.shape[1]
    E = enum_pad
    lo_t = lo_ref[...]                                  # [BQ * 16, Bb] f32
    hi_t = hi_ref[...]
    jidx = jax.lax.broadcasted_iota(jnp.int32, (1, E), 1)
    biota = jax.lax.broadcasted_iota(jnp.int32, (Bb, E), 0)
    wiota = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_WORDS, E), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BP), 1)

    def body(p, hit):
        pmin_p = pmin_ref[0, p]
        w_p = width_ref[0, p]

        def probe(_):
            cand = pmin_p + jidx                        # [1, E] int32
            # int64 fold: the high word of an int32-domain key is its
            # sign extension (cand >> 31 == 0 or -1 == 0xFFFFFFFF).
            h0 = mix32(cand ^ mix32(cand >> 31))
            h1 = mix32(h0 ^ jnp.int32(H1_SALT))
            h2 = mix32(h1 ^ jnp.int32(H2_SALT))
            block = h0 & jnp.int32(Bb - 1)
            onehot = (biota == block).astype(jnp.float32)       # [Bb, E]
            # Exact gather of the candidates' blocks for all BQ filters
            # at once: one 1.0 per column; halves are <= 0xFFFF so the
            # f32 dot is an exact row select, reassembled in int32 — at
            # HIGHEST precision only: the TPU's default single bf16 pass
            # keeps 8 of the 16 bits (measured on a v5e).
            glo = jnp.dot(lo_t, onehot, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
            ghi = jnp.dot(hi_t, onehot, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
            word = (ghi.astype(jnp.int32) << 16) | glo.astype(jnp.int32)
            sig = jnp.zeros((BLOCK_WORDS, E), jnp.int32)
            for i in range(K_PROBES):
                wi = lsr32(h1, 8 * i) & jnp.int32(BLOCK_WORDS - 1)
                bi = lsr32(h2, 8 * i) & jnp.int32(31)
                sig |= jnp.where(wiota == wi,
                                 jnp.left_shift(jnp.int32(1), bi), 0)
            word = word.reshape(BQ, BLOCK_WORDS, E)
            ok = jnp.all((word & sig[None]) == sig[None], axis=1)   # [BQ, E]
            return jnp.any(ok & (jidx < w_p), axis=1,
                           keepdims=True).astype(jnp.int32)          # [BQ, 1]

        h = jax.lax.cond(w_p > 0, probe,
                         lambda _: jnp.ones((BQ, 1), jnp.int32), None)
        # lane-dense write of partition p's column: a select, not a scatter
        return jnp.where(lane == p, h, hit)

    hit_ref[...] = jax.lax.fori_loop(0, BP, body,
                                     jnp.ones((BQ, BP), jnp.int32))


@functools.partial(jax.jit, static_argnames=("enum_pad", "interpret"))
def bloom_probe_batched(
    lo_t: jax.Array,     # [Q, 16, Bb] f32 low 16-bit filter-word halves
    hi_t: jax.Array,     # [Q, 16, Bb] f32 high halves (ops.pack_blooms)
    pmin: jax.Array,     # [P] int32 resident integer-snapped minima
    width: jax.Array,    # [P] int32 candidate counts; 0 = keep (no enum)
    enum_pad: int,       # lane bucket >= every width (pow2, ops.enum_bucket)
    interpret: bool = False,
) -> jax.Array:
    """Batched Bloom probe: Q build filters x P probe partitions.

    Returns hit [Q, P] int32 — 0 only where partition p is enumerable
    (0 < width[p] <= enum_pad) and none of its candidate values is in
    query q's filter.  Row q is bit-identical to the host matcher's
    narrow-range enumeration for the same filter.

    Layout: a grid cell holds BLOCK_QF filters' words stacked on the
    sublane dim ([BLOCK_QF * 16, Bb], one matmul gathers for all of them)
    and writes a lane-dense [BLOCK_QF, BLOCK_PB] hit tile; the per-
    partition enumeration scalars ride in SMEM.
    """
    P = pmin.shape[0]
    Q, _w16, Bb = lo_t.shape
    pad_q = (-Q) % BLOCK_QF
    if pad_q:
        # all-zero filters: never a hit; sliced off below.
        lo_t = jnp.pad(lo_t, ((0, pad_q), (0, 0), (0, 0)))
        hi_t = jnp.pad(hi_t, ((0, pad_q), (0, 0), (0, 0)))
    pad_p = (-P) % BLOCK_PB
    if pad_p:
        # width 0 -> hit 1 without probing; sliced off below.
        pmin = jnp.pad(pmin, (0, pad_p))
        width = jnp.pad(width, (0, pad_p))
    Qp, Pp = Q + pad_q, P + pad_p
    rows = BLOCK_QF * BLOCK_WORDS
    grid = (Qp // BLOCK_QF, Pp // BLOCK_PB)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    hit = pl.pallas_call(
        functools.partial(_bloom_probe_kernel, enum_pad=enum_pad),
        grid=grid,
        in_specs=[
            smem((None, 1, BLOCK_PB), lambda q, p: (p, 0, 0)),
            smem((None, 1, BLOCK_PB), lambda q, p: (p, 0, 0)),
            pl.BlockSpec((rows, Bb), lambda q, p: (q, 0)),
            pl.BlockSpec((rows, Bb), lambda q, p: (q, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_QF, BLOCK_PB), lambda q, p: (q, p)),
        out_shape=jax.ShapeDtypeStruct((Qp, Pp), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="bloom_probe_batched",
    )(pmin.reshape(-1, 1, BLOCK_PB), width.reshape(-1, 1, BLOCK_PB),
      lo_t.reshape(Qp * BLOCK_WORDS, Bb),
      hi_t.reshape(Qp * BLOCK_WORDS, Bb))
    return hit[:Q, :P]
