"""The served path's batched kernels compile for a TPU v5e chip.

Interpret mode accepts block shapes and memory footprints the chip's
compiler refuses, so the parity suites cannot see them.  These tests
compile each batched kernel for one chip of a described ``v5e:2x2``
topology — no chip attached — at the size of a real table (P = 2**20
micro-partitions) and the batch sizes the service produces, and check
that the program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every worker of a
parallel run imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.core.device_stats import KPLANE, plane_capacity
from repro.core.prune_join import BLOCK_WORDS, DEFAULT_ENUM_LIMIT
from repro.kernels import (bloom_probe_batched, join_overlap_batched,
                           minmax_prune_batched, ops, topk_init_batched)
from repro.serve.prune_service import TOPK_INIT_MAX_K

P = 1 << 20
C = 6                    # the events table's columns
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _compile(fn, args, **static):
    lowered = jax.jit(fn, static_argnames=tuple(static)).lower(*args,
                                                              **static)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


Q_BUCKETS = [ops.q_bucket(q) for q in (8, 64, 256)]


@pytest.mark.parametrize("Q", Q_BUCKETS)
def test_minmax_prune_batched_compiles(one_chip, Q):
    S = _spec(one_chip)
    Kb = 4
    _compile(minmax_prune_batched,
             (S((Q, Kb), I32), S((Q, Kb), F32), S((Q, Kb), F32),
              S((C, P), F32), S((C, P), F32), S((C, P), F32)))


@pytest.mark.parametrize("Q", Q_BUCKETS)
def test_flat_verdict_epilogue_compiles(one_chip, Q):
    """The flat launch's epilogue at the events table's size: the
    kernel's int32 verdicts over the plane's capacity (2**21 for
    P = 2**20) in, int8 over the live partitions out, four to a word."""
    S = _spec(one_chip)
    Pc = plane_capacity(P)
    W = ops.verdict_width(P, Pc)
    assert W == P < Pc
    text = ops._narrow_verdicts.lower(
        S((Q, Pc), I32), S((Q,), jnp.bool_), width=W).compile().as_text()
    entry = next(line for line in text.splitlines() if "ENTRY" in line)
    assert entry.endswith(f"-> s32[{Q},{W // 4}] {{"), entry


@pytest.mark.parametrize("limbs", [2, 3])
def test_minmax_prune_batched_compiles_on_limbs(one_chip, limbs):
    """A launch with a wide column: ``limbs`` slots per constraint and
    the column's limb rows after the six primary ones."""
    S = _spec(one_chip)
    Q, Kb, rows = ops.q_bucket(64), 4, C + limbs
    _compile(minmax_prune_batched,
             (S((Q, Kb * limbs), I32), S((Q, Kb * limbs), F32),
              S((Q, Kb * limbs), F32), S((rows, P), F32),
              S((rows, P), F32), S((rows, P), F32)), limbs=limbs)


@pytest.mark.parametrize("Db", [ops.d_bucket(1), ops.d_bucket(4096)])
@pytest.mark.parametrize("Q", Q_BUCKETS)
def test_join_overlap_batched_compiles(one_chip, Q, Db):
    S = _spec(one_chip)
    _compile(join_overlap_batched, (S((Q, Db), F32), S((P,), F32),
                                    S((P,), F32)))


@pytest.mark.parametrize("Q", Q_BUCKETS)
def test_topk_init_batched_compiles(one_chip, Q):
    S = _spec(one_chip)
    _compile(topk_init_batched, (S((P, KPLANE), F32), S((Q, P), F32)),
             k=ops.k_bucket(TOPK_INIT_MAX_K))


@pytest.mark.parametrize("Q,Bb,E", [
    *[(Q, ops.bloom_bucket(1), ops.enum_bucket(1)) for Q in Q_BUCKETS],
    # the worst bucket: the largest filter the kernel path takes and the
    # widest enumeration — the [Bb, E] one-hot tile against fast memory
    (Q_BUCKETS[-1], ops.bloom_bucket(ops.BLOOM_MAX_BLOCKS),
     ops.enum_bucket(DEFAULT_ENUM_LIMIT)),
])
def test_bloom_probe_batched_compiles(one_chip, Q, Bb, E):
    S = _spec(one_chip)
    _compile(bloom_probe_batched,
             (S((Q, BLOCK_WORDS, Bb), F32), S((Q, BLOCK_WORDS, Bb), F32),
              S((P,), I32), S((P,), I32)), enum_pad=E)
