"""Shared helpers of the benchmark's CPU tests: small sizes of each
configuration, and a run of a cell through everything but the chip."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# Small enough for a CPU test, large enough that the events table still
# takes the tree rungs (P >= 4 groups of 256) and every TPC-H partition
# still has 1,024 rows.
SMALL = {
    "events-1m": {"events_partitions": 4096},
    "tpch-sf1000": {"lineitem_partitions": 48, "orders_partitions": 16},
}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]
CONFIG_OF = {w["name"]: w["config"] for w in SPEC["workloads"]}
SEED = 3_000_000_019          # wider than 32 bits, as the driver's are


def run_small(cell: str, seconds=None, trace: bool = False,
              root: str = ROOT, seed: int = SEED, config=None, overrides=None):
    """One run of ``cell`` on the CPU: 1 s for a closed loop, 3 s for an
    open loop, whose first micro-batch waits out the front end's deadline."""
    from bench import harness, spec
    config = config or CONFIG_OF[cell]
    if seconds is None:
        bench = spec.Benchmark(root)
        kind = bench.traffic(bench.workload(cell)["traffic"])["loop"]["kind"]
        seconds = 3.0 if kind == "open" else 1.0
    return harness.run_cell(root, cell, seed, seconds, trace, mode="auto",
                            t_start=time.perf_counter(),
                            overrides=dict(SMALL.get(config, {}),
                                           **(overrides or {})))


def small_tables(config: str, seed: int = SEED):
    from bench import spec
    from bench.trafficgen import rng_for
    bench = spec.Benchmark(ROOT)
    cfg = dict(bench.config(config), **SMALL[config])
    return spec.generator(cfg["generator"]).generate(cfg, rng_for(seed, 0))
