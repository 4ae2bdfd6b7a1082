"""The Iceberg event table: microsecond timestamps the check holds exact.

Its ``ts`` is an int64 of microseconds in 2024, ~1.7e15: exact in
float64, moved by up to 2**27 us by a float32 cast.  The program has to
match the float64 reference on the cell's traffic, and the reference
computed in float32 has to fail the same comparison, or the check could
not tell an exact device path from a rounded one.
"""

import numpy as np
import pytest

from bench.tests.common import ROOT, SEED, run_small, small_tables

pytestmark = pytest.mark.filterwarnings("ignore")

CONFIG = "events-1m-iceberg"
CELL = "events-1m-iceberg.recent-batch"
T_START = 1_704_067_200_000_000        # 2024-01-01T00:00:00Z
T_END = 1_735_689_600_000_000          # 2025-01-01T00:00:00Z


def test_generated_ts_is_integral_2024_microseconds():
    data = small_tables(CONFIG)
    assert set(data) == {"events"}
    ev = data["events"]
    ts = ev.columns["ts"]
    assert ev.kinds["ts"] == "int" and ev.rows_per_partition == 8
    assert np.array_equal(ts, np.round(ts))
    assert ts.min() >= T_START and ts.max() < T_END
    # exact in float64: the int64 values survive the round trip
    assert np.array_equal(ts.astype(np.int64).astype(np.float64), ts)
    assert ts.max() < 2.0 ** 53 and ts.min() > 2.0 ** 24
    # the same six columns as events-1m's events table
    assert list(ev.columns) == ["ts", "user_id", "score", "num_sightings",
                                "status", "region"]


def test_program_matches_the_reference_exactly():
    """A run of the cell (mode ``auto``: the jnp reference kernels off
    the TPU) matches the float64 reference on every checked request."""
    r = run_small(CELL, seconds=2.0)
    assert r["correct"] is True and r["failed"] == 0
    assert r["checks"]["mismatches"]["value"] == 0
    assert r["checks"]["checked"]["value"] > 0


def test_a_float32_reference_reads_as_mismatches():
    """At 2**16 partitions each spans 1/16 of the time it spans at 2**12,
    so window edges fall within float32's 134 s spacing of a partition
    bound often enough to count: 19 of 96 checked requests differ at this
    seed (6 of the first 8 at the cell's own 2**20)."""
    from bench import control, harness
    data, mix, kept = control.control_outcomes(
        ROOT, CELL, SEED, batches=24, overrides={"events_partitions": 1 << 16},
        dtype=np.float32)
    ver = harness.verify(data, kept, mix["check"]["sample"], SEED)
    assert ver["checked"] == 96 and ver["mismatches"] > 0
