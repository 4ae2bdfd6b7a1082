"""Pallas TPU kernels (+ ops wrappers, ref oracles).

Pruning hot spots (the paper's engine):
  minmax_prune         — conjunctive-range three-valued filter pruning (Sec. 3)
  minmax_prune_batched — Q queries x K ranges x P partitions in one launch,
                         against the resident metadata plane (device_stats)
  topk_boundary        — WAND-style boundary scan over block top-k rows (Sec. 5)
  topk_init_batched    — Q queries' upfront boundaries (Sec. 5.4) over the
                         resident block-top-k plane in one launch
  join_overlap         — distinct-keys vs partition-range overlap (Sec. 6)
  join_overlap_batched — Q build summaries x P probe partitions against the
                         resident join-key plane in one launch
  bloom_probe_batched  — Q blocked-Bloom filters x P probe partitions in one
                         launch: narrow-range enumeration against the
                         resident enumeration plane (Sec. 6, large-NDV path)

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU with interpret=True against the pure-jnp oracles in
ref.py.
"""

from . import ops, ref
from .bloom_probe import bloom_probe_batched
from .join_overlap import join_overlap, join_overlap_batched
from .minmax_prune import minmax_prune
from .minmax_prune_batched import minmax_prune_batched
from .topk_boundary import topk_boundary, topk_init_batched

__all__ = ["ops", "ref", "minmax_prune", "minmax_prune_batched",
           "topk_boundary", "topk_init_batched", "join_overlap",
           "join_overlap_batched", "bloom_probe_batched"]
