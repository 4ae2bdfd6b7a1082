"""Top-k analytics over a logged-requests table.

Serving deployments keep request logs, and `ORDER BY ... LIMIT k` over
them is the workload the paper's top-k pruning (Sec. 5) accelerates.
This example serves such queries with boundary-value pruning and
compares the bytes read against the full scan.

Run:  PYTHONPATH=src python examples/topk_serving.py
"""

import time

import numpy as np

from repro.core import expr as E
from repro.core.flow import PruningPipeline, Query, TableScanSpec
from repro.data.generator import ColumnSpec, gen_table
from repro.data.scan import execute_query

rng = np.random.default_rng(0)

requests = gen_table(
    "requests", rng, n_rows=200_000, rows_per_partition=1000,
    specs=[
        ColumnSpec("ts", "int", 0, 10_000_000, clustering=0.99),
        ColumnSpec("latency_ms", "float", 1.0, 5000.0, clustering=0.35),
        ColumnSpec("model", "str", n_distinct=8, clustering=0.2,
                   str_groups=("lm", "vlm")),
        ColumnSpec("tokens_out", "int", 1, 4096, clustering=0.0),
    ],
)

pipe = PruningPipeline()
queries = [
    ("slowest requests today",
     Query(scans={"requests": TableScanSpec(requests, E.col("ts") >= 9_000_000)},
           limit=20, order_by=("requests", "latency_ms", True))),
    ("top token producers",
     Query(scans={"requests": TableScanSpec(requests)},
           limit=10, order_by=("requests", "tokens_out", True))),
]
for name, q in queries:
    t0 = time.perf_counter()
    rep = pipe.run(q)
    res = execute_query(q, rep)
    dt = (time.perf_counter() - t0) * 1e3
    base = execute_query(q, None)
    t = rep.per_scan["requests"].get("topk")
    skipped = len(rep.topk.skipped) if rep.topk is not None else 0
    print(f"{name}: {skipped} of "
          f"{t.before if t else '?'} partitions skipped "
          f"({res.total_bytes()/1e6:.1f} MB vs {base.total_bytes()/1e6:.1f} MB "
          f"unpruned) in {dt:.0f} ms")

