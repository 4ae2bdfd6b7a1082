"""The control of the correctness check: the reference one precision down.

The configurations state float32 stat planes.  The control puts the plain
reference, computed in bfloat16 (every value and literal rounded to it),
in the program's place, on the requests a run of the cell would check,
and compares it with the float64 reference by the run's own comparison.
A check that cannot tell the two apart would pass a program that pruned
on rounded stats; the control has to read as mismatches.

    python3 -m bench.control --workload <cell> --seeds 1 2 3 [--batches N]

Prints one JSON line per seed: ``{"seed", "checked", "mismatches"}``.
It needs no chip; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import ml_dtypes
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_outcomes(root: str, workload: str, seed: int, batches: int,
                     overrides=None, dtype=ml_dtypes.bfloat16):
    """(plain query, lower-precision Outcome) for what a run would keep."""
    from . import spec
    from .reference import Reference
    from .trafficgen import Traffic, rng_for
    bench = spec.Benchmark(root)
    cell = bench.workload(workload)
    cfg = dict(bench.config(cell["config"]), **(overrides or {}))
    mix = bench.traffic(cell["traffic"])
    data = spec.generator(cfg["generator"]).generate(cfg, rng_for(seed, 0))
    traffic = Traffic(mix, seed)
    low = Reference(data, dtype=dtype)
    if traffic.loop["kind"] == "open":
        qs = traffic.queries(len(traffic.arrivals(batches)), stream=0)
    else:
        keep = mix["check"].get("per_batch", 4)
        qs = []
        for b, batch in zip(range(batches), traffic.batches(traffic.loop["batch"])):
            pos = rng_for(seed, 3, b).choice(len(batch), size=min(keep, len(batch)),
                                             replace=False)
            qs += [batch[i] for i in sorted(pos)]
    return data, mix, [(q, low.outcome(q)) for q in qs]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--batches", type=int, default=30,
                    help="closed loop: batches a run completes; open loop: "
                         "window seconds")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from .harness import verify
    for seed in args.seeds:
        data, mix, kept = control_outcomes(ROOT, args.workload, seed,
                                           args.batches)
        ver = verify(data, kept, mix["check"]["sample"], seed, np.float64)
        print(json.dumps(dict(seed=seed, checked=ver["checked"],
                              mismatches=ver["mismatches"],
                              first=ver["notes"][:2])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
