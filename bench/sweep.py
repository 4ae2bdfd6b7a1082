"""Offered-load sweep of an open-loop mix, to find the rate it sustains.

    python3 -m bench.sweep --config <config> --traffic <open-loop mix> \
        --seed <n> --seconds <s> --rates 4 6 8 10 [--deadline <s>]

It reads the configuration and the mix by name, so a mix is swept before
any cell in ``BENCHMARK.json`` uses it.  One process sets up once, then
offers each rate for ``--seconds`` through the same front end and service
and prints one JSON line per rate: offered and completed queries/s,
p50/p95 from due times, batches and their median size, and how far the
backlog grew (mean latency of the last quarter of arrivals over the first
quarter's).  A rate is sustained when every request finished and the
backlog did not grow; requests still in flight when the window closes are
not lost, so the completions inside the window are not the test.  The last
line names the highest sustained rate.  The cell's rate is then fixed in
its mix file; the benchmark never searches for one.  Needs the chip, like
``bench.run``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKLOG_GROWTH_OK = 1.25      # sustained: the last quarter waits at most this
BACKLOG_GROWTH_STOP = 2.0     # past capacity: stop before the backlog spills


def backlog_growth(latencies) -> float:
    """Mean latency of the last quarter of arrivals over the first's."""
    q = max(len(latencies) // 4, 1)
    return statistics.mean(latencies[-q:]) / statistics.mean(latencies[:q])


def sustained(unfinished: int, growth: float) -> bool:
    return bool(unfinished == 0 and growth <= BACKLOG_GROWTH_OK)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--deadline", type=float, default=None,
                    help="front-end deadline to try instead of the mix's")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("bench.sweep: needs a TPU", file=sys.stderr)
        return 3
    from repro.serve.prune_service import PruningService

    from bench import harness, program, spec
    from bench.trafficgen import Traffic, rng_for
    bench = spec.Benchmark(ROOT)
    cfg = bench.config(args.config)
    mix = bench.traffic(args.traffic)
    if args.deadline is not None:
        mix["loop"]["deadline_s"] = args.deadline
    data = spec.generator(cfg["generator"]).generate(cfg, rng_for(args.seed, 0))
    tables = {n: program.build_table(td) for n, td in data.items()}
    svc = PruningService(verdict_cache=cfg["verdict_cache"])
    counter = harness.CompileCounter()
    harness.warm_up(svc, Traffic(mix, args.seed), tables, mix["warmup"])
    print(json.dumps(dict(setup_s=time.perf_counter() - T_START,
                          compiles=counter.n)), flush=True)
    best = None
    for i, rate in enumerate(args.rates):
        m = copy.deepcopy(mix)
        m["loop"]["rate_qps"] = rate
        n0 = counter.n
        out = harness.open_loop(svc, Traffic(m, args.seed + i + 1), tables,
                                args.seconds, traced=False)
        lat = [r["latency_ms"] for r in out["requests"]
               if r["latency_ms"] is not None]
        sizes = {}
        for r in out["requests"]:
            if r["launched"] is not None:
                key = (r["launched"], r["done"])
                sizes[key] = sizes.get(key, 0) + 1
        e2e = harness.end_to_end(out, 0.0)
        unfinished = out["attempted"] - len(lat)
        growth = float(backlog_growth(lat)) if lat else float("inf")
        ok = sustained(unfinished, growth)
        if ok:
            best = rate
        print(json.dumps(dict(
            offered_qps=rate, completed_qps=e2e["qps"],
            attempted=out["attempted"], unfinished=unfinished,
            p50_ms=e2e["p50_ms"], p95_ms=e2e["p95_ms"],
            batches=len(sizes),
            median_batch=statistics.median(sizes.values()) if sizes else 0,
            backlog_growth=growth, sustained=ok,
            deadline_s=m["loop"]["deadline_s"], compiles=counter.n - n0,
            compiled=sorted(set(counter.names[n0:])))), flush=True)
        if unfinished or growth > BACKLOG_GROWTH_STOP:
            break
    print(json.dumps(dict(highest_sustained_qps=best)), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # as in bench.run: skip the teardown, where the runtime's threads have
    # crashed a finished process
    os._exit(code)
