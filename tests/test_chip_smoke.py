"""chip_smoke.py's core on the CPU, and no silent demotion off the TPU.

The smoke's phases run here at a tiny P with the Pallas kernels in
interpret mode — the same served path, parity rules and zero-demotion
checks as on the chip.  An explicit ``mode="pallas"`` has no CPU meaning:
it raises instead of interpreting, and the smoke's CLI refuses a host
without a TPU.
"""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.device_stats import DeviceStats
from repro.core.metadata import ColumnMeta, PartitionStats
from repro.core.prune_join import BlockedBloom
from repro.kernels import ops
from repro.serve.prune_service import PruningService

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402


def test_smoke_core_interpret_mode_tiny_table():
    summary = chip_smoke.run_smoke(mode="interpret", P=2048, q_sizes=(16,),
                                   log=lambda s: None)
    launches = summary["launches"]
    for name in ("filter_flat", "filter_tree", "join", "join_bloom", "topk"):
        assert launches[name] > 0, name
    assert not any(summary["fallbacks"].values())
    assert not any(summary["demotions"].values())
    assert summary["passthroughs"] == summary["errors"] == 0
    assert summary["salvaged_batches"] == 0
    assert summary["checked_queries"] == 6 * 16
    assert all(summary["kernel_parity"].values())


def test_smoke_cli_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def _stats(P=64, C=2):
    rng = np.random.default_rng(0)
    mins = rng.integers(0, 1000, (P, C)).astype(np.float64)
    return PartitionStats(
        columns=[ColumnMeta(f"c{i}", "int") for i in range(C)],
        mins=mins, maxs=mins + 5, null_counts=np.zeros((P, C), np.int64),
        row_counts=np.full(P, 8, np.int64))


def _bloom():
    b = BlockedBloom(100)
    b.add(np.arange(100))
    return b


PALLAS_CALLS = {
    "filter": lambda: ops.prune_ranges_batched_device(
        [[(0, 10.0, 500.0)]], DeviceStats.stage(_stats()), mode="pallas"),
    "join": lambda: ops.join_overlap_batched_device(
        [np.arange(5, dtype=np.float32)], jnp.zeros(64), jnp.ones(64),
        mode="pallas"),
    "join_bloom": lambda: ops.bloom_probe_batched_device(
        [_bloom()], jnp.zeros(64, jnp.int32), jnp.ones(64, jnp.int32), 1,
        64, mode="pallas"),
    "topk": lambda: ops.topk_init_batched_device(
        jnp.zeros((64, 8)), np.ones((1, 64), np.float32), 4, mode="pallas"),
    "service": lambda: PruningService(mode="pallas"),
}


@pytest.mark.parametrize("name", sorted(PALLAS_CALLS))
def test_pallas_mode_without_tpu_raises(name):
    with pytest.raises(RuntimeError, match="TPU"):
        PALLAS_CALLS[name]()
