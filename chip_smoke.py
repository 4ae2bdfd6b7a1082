#!/usr/bin/env python3
"""Bring-up smoke of the served pruning path on one TPU.

Drives the path a warehouse's query planners call —
``ServingFrontend(PruningService())`` with the default ``mode="auto"`` and
verdict cache — over one fact table at the size one chip holds: the
``events`` table at P = 2**20 micro-partitions (8 rows each, so every
partition has min < max) plus the ``users`` dimension, all generated from
a fixed seed.  Its resident metadata (stat, tree, join-key, enumeration
and block-top-k planes) is about 0.7 GB of HBM: the pruning metadata of
one 16-500 TB table.

Phases, in one process:

  kernels   each batched Pallas kernel on the chip against its jnp oracle
            (``kernels/ref.py``), bit for bit, on inputs that need every
            f32 mantissa bit of the one-hot matmul gathers
  data      generate the tables
  serve     batches of Q = 64 and 256 queries through the front-end:
            selective filters and LIMITs (the tree rung), dense filters
            (the flat kernel), and the mixed filter / LIMIT / top-k /
            distinct-join / Bloom-join traffic of
            ``benchmarks/bench_runtime_prune.py``; every report is checked
            against ``PruningPipeline(filter_mode="host")``
  counters  launches > 0 for the flat filter, the tree filter, join,
            join_bloom and topk; no fallback, demotion, passthrough,
            error or salvaged batch anywhere

Any miss exits non-zero and names what missed (and, for a demotion, the
rung and its exception).  The last line of stdout is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``; a failed run
prints none.  There is no CPU path: without a TPU the script exits 2.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only the partition-sharded
                                       # service on four chips, against the
                                       # unsharded service and the host
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

P_FULL = 1 << 20              # events micro-partitions
ROWS_PER_PARTITION = 8
USERS_ROWS = 50_000           # 15-year age windows exceed the 4096-key
                              # distinct limit (Bloom joins), 5-year ones
                              # stay under it (distinct joins)
# Displacement is a fraction of the table's rows, so at 8.4M rows these
# give each partition a ts span of ~280 of 10M (ingestion-ordered events)
# and a user_id span of ~15 ids (narrow enough for the Bloom join's
# enumeration to decide every partition).
TS_CLUSTERING = 0.99999
USER_CLUSTERING = 0.99999
Q_SIZES = (64, 256)
SEED = 0


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def _import_engine():
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import numpy as np  # noqa: F401
    from repro.core import flow  # noqa: F401
    from benchmarks import bench_runtime_prune  # noqa: F401


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------

def build_tables(P: int, seed: int):
    import numpy as np

    from repro.data.generator import make_events_table, make_users_table
    rng = np.random.default_rng(seed)
    events = make_events_table(
        rng, n_rows=P * ROWS_PER_PARTITION,
        rows_per_partition=ROWS_PER_PARTITION,
        ts_clustering=TS_CLUSTERING, user_clustering=USER_CLUSTERING)
    users = make_users_table(rng, n_rows=USERS_ROWS)
    return events, users


def filter_queries(Q: int, events, rng, dense: bool):
    """Filter and plain-LIMIT queries on ``events`` only.

    Selective windows are the bench's production shape (a lognormal
    fraction around 0.4% of the ts range); dense ones keep 60-95% of it,
    so the tree rung's coarse survivor density is past its cutoff and the
    flat kernel answers."""
    import numpy as np

    from benchmarks.bench_runtime_prune import TS_MAX
    from repro.core import expr as E
    from repro.core.flow import Query, TableScanSpec
    qs = []
    for i in range(Q):
        if dense:
            frac = float(rng.uniform(0.6, 0.95))
        else:
            frac = min(float(np.exp(rng.normal(np.log(0.004), 1.0))), 1.0)
        lo = TS_MAX * (1 - frac)
        pred = (E.col("ts") >= lo) & (E.col("ts") <= TS_MAX) \
            & (E.col("user_id") >= 1000) & (E.col("num_sightings") >= 0)
        qs.append(Query(scans={"events": TableScanSpec(events, pred)},
                        limit=int(rng.integers(5, 20)) if i % 4 == 3
                        else None))
    return qs


def mixed_queries(Q: int, events, users, rng):
    """The bench's mixed traffic with one query in eight a Bloom join."""
    from benchmarks.bench_runtime_prune import make_bloom_queries, make_queries
    qs = make_queries(Q, events, users, rng)
    for i in range(6, Q, 8):
        qs[i] = make_bloom_queries(1, events, users, rng)[0]
    return qs


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def check_against_host(label, reports, host_reports) -> int:
    """Scan sets bit-identical to the host pipeline; top-k values equal
    and the device boundary only ever adds skips.  Returns the count of
    checked queries."""
    import numpy as np
    for qi, (rep, host) in enumerate(zip(reports, host_reports)):
        if set(rep.scan_sets) != set(host.scan_sets):
            raise SmokeFailure(f"{label} q{qi}: scans differ")
        for name, hs in host.scan_sets.items():
            ds = rep.scan_sets[name]
            if not (np.array_equal(ds.part_ids, hs.part_ids)
                    and np.array_equal(ds.match, hs.match)):
                raise SmokeFailure(
                    f"{label} q{qi}: scan set {name!r} differs from the "
                    f"host pipeline ({len(ds)} vs {len(hs)} partitions)")
        if (rep.topk is None) != (host.topk is None):
            raise SmokeFailure(f"{label} q{qi}: top-k presence differs")
        if host.topk is not None:
            if not np.array_equal(rep.topk.values, host.topk.values):
                raise SmokeFailure(f"{label} q{qi}: top-k values differ")
            if not set(host.topk.skipped) <= set(rep.topk.skipped):
                raise SmokeFailure(
                    f"{label} q{qi}: device top-k skipped fewer partitions")
    return len(reports)


def reports_identical(a, b) -> bool:
    """Bit-identical pruning outcome, top-k skips included."""
    import numpy as np
    if set(a.scan_sets) != set(b.scan_sets):
        return False
    for n in a.scan_sets:
        if not (np.array_equal(a.scan_sets[n].part_ids,
                               b.scan_sets[n].part_ids)
                and np.array_equal(a.scan_sets[n].match,
                                   b.scan_sets[n].match)):
            return False
    if (a.topk is None) != (b.topk is None):
        return False
    return a.topk is None or (
        np.array_equal(a.topk.values, b.topk.values)
        and np.array_equal(a.topk.skipped, b.topk.skipped))


def kernel_parity(mode: str, seed: int) -> dict:
    """Each batched kernel against its jnp oracle on a small input.

    Stat values reach 1e7 and Bloom word halves 0xFFFF, so a one-hot
    matmul gather that dropped mantissa bits would show here as a
    mismatch, separately from the served phases."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.prune_join import BlockedBloom
    from repro.kernels import (bloom_probe_batched, join_overlap_batched,
                               minmax_prune_batched, ops, ref,
                               topk_init_batched)
    interpret = ops.kernel_interpret(mode)
    rng = np.random.default_rng(seed)
    out = {}

    P, C, Q, Kb = 4096 + 77, 6, 16, 4
    lo_s = rng.integers(0, 10_000_000, (C, P)).astype(np.float32)
    mins = jnp.asarray(lo_s)
    maxs = jnp.asarray(lo_s + rng.integers(0, 5000, (C, P)).astype(np.float32))
    dem = jnp.asarray((rng.random((C, P)) < 0.1).astype(np.float32))
    cids = jnp.asarray(rng.integers(0, C, (Q, Kb)).astype(np.int32))
    qlo = rng.integers(0, 10_000_000, (Q, Kb)).astype(np.float32)
    lo = jnp.asarray(qlo)
    hi = jnp.asarray(qlo + rng.integers(0, 3_000_000, (Q, Kb)).astype(
        np.float32))
    got = minmax_prune_batched(cids, lo, hi, mins, maxs, dem,
                               interpret=interpret)
    want = ref.minmax_prune_batched_ref(cids, lo, hi, mins, maxs, dem)
    out["minmax_prune_batched"] = bool(np.array_equal(got, want))

    pmin = rng.integers(0, 10_000_000, P).astype(np.float32)
    pmax = pmin + rng.integers(0, 200, P).astype(np.float32)
    for n_keys in (16, 300):
        lists = [np.unique(rng.integers(0, 10_000_000, n_keys)).astype(
            np.float32) for _ in range(Q)]
        dist = jnp.asarray(ops.pack_distinct(lists))
        got = join_overlap_batched(dist, jnp.asarray(pmin), jnp.asarray(pmax),
                                   interpret=interpret)
        want = ref.join_overlap_batched_ref(dist, jnp.asarray(pmin),
                                            jnp.asarray(pmax))
        out[f"join_overlap_batched[Db={dist.shape[1]}]"] = bool(
            np.array_equal(got, want))

    K = 64
    plane = np.sort(rng.integers(-10_000_000, 10_000_000, (P, K)).astype(
        np.float32), axis=1)[:, ::-1]
    plane[rng.random(P) < 0.2, 40:] = -np.inf
    mask = (rng.random((Q, P)) < 0.01).astype(np.float32)
    for k in (1, 16, 128):
        got = topk_init_batched(jnp.asarray(plane), jnp.asarray(mask), k,
                                interpret=interpret)
        want = ref.topk_init_batched_ref(jnp.asarray(plane),
                                         jnp.asarray(mask), k)
        out[f"topk_init_batched[k={k}]"] = bool(np.array_equal(got, want))

    blooms = []
    for _ in range(Q):
        keys = np.unique(rng.integers(-200_000, 200_000,
                                      int(rng.integers(50, 30_000))))
        b = BlockedBloom(len(keys))
        b.add(keys)
        blooms.append(b)
    lo_t, hi_t = ops.pack_blooms(blooms)
    epmin = jnp.asarray(rng.integers(-200_000, 200_000, P).astype(np.int32))
    width = rng.integers(0, 120, P).astype(np.int32)
    width[rng.random(P) < 0.3] = 0
    width = jnp.asarray(width)
    got = bloom_probe_batched(jnp.asarray(lo_t), jnp.asarray(hi_t), epmin,
                              width, enum_pad=128, interpret=interpret)
    want = ref.bloom_probe_batched_ref(jnp.asarray(lo_t), jnp.asarray(hi_t),
                                       epmin, width, 128)
    out[f"bloom_probe_batched[Bb={lo_t.shape[2]}]"] = bool(
        np.array_equal(got, want))
    return out


def _snapshot(svc) -> dict:
    from repro.serve.resilience import resilience_snapshot
    return dict(counters=svc.counters.snapshot(),
                resilience=resilience_snapshot(svc.resilience))


def _delta(before: dict, after: dict) -> dict:
    from repro.serve.prune_service import ServiceCounters
    from repro.serve.resilience import resilience_delta
    return dict(counters=ServiceCounters.delta(before["counters"],
                                               after["counters"]),
                resilience=resilience_delta(before["resilience"],
                                            after["resilience"]))


def _serve(fe, queries):
    futs = [fe.submit(q) for q in queries]
    fe.drain()
    return [f.result().report for f in futs]


def run_smoke(mode: str = "auto", P: int = P_FULL,
              q_sizes=Q_SIZES, seed: int = SEED, log=print) -> dict:
    """The smoke's phases; raises SmokeFailure listing every miss.

    ``mode`` is the service's kernel mode: ``"auto"`` on the chip, and
    ``"interpret"`` where a test runs the same path on the CPU."""
    import numpy as np

    from repro.core.flow import PruningPipeline
    from repro.serve.frontend import ServingFrontend
    from repro.serve.prune_service import PruningService

    failures = []
    summary = dict(P=P, q_sizes=list(q_sizes))

    t0 = time.perf_counter()
    try:
        parity = kernel_parity(mode, seed)
    except Exception as exc:  # noqa: BLE001 — report and go on
        parity = {}
        failures.append(f"kernels: {type(exc).__name__}: {exc}")
    summary["kernel_parity"] = parity
    log(f"kernels: {json.dumps(parity)} "
        f"({time.perf_counter() - t0:.3f}s incl. compile)")
    failures += [f"kernels: {k} differs from its jnp oracle"
                 for k, ok in parity.items() if not ok]

    t0 = time.perf_counter()
    events, users = build_tables(P, seed)
    summary["generate_s"] = time.perf_counter() - t0
    cut = "no cut" if P >= P_FULL else f"cut from P={P_FULL}"
    log(f"data: events P={events.num_partitions} "
        f"({events.num_rows} rows, {ROWS_PER_PARTITION} per partition; "
        f"{cut}), users P={users.num_partitions}; generated in "
        f"{summary['generate_s']:.3f}s")

    svc = PruningService(mode=mode)
    host = PruningPipeline(filter_mode="host")
    rng = np.random.default_rng(seed + 1)
    start = _snapshot(svc)
    batches = []
    flat_filter = tree_filter = 0
    checked = 0
    for Q in q_sizes:
        # one micro-batch per Q queries: the size cap fires, never the
        # deadline
        with ServingFrontend(svc, max_batch=Q, deadline_s=3600.0) as fe:
            for kind in ("selective", "dense", "mixed"):
                for rep_i in range(2):
                    label = f"Q={Q} {kind}#{rep_i}"
                    if kind == "mixed":
                        qs = mixed_queries(Q, events, users, rng)
                    else:
                        qs = filter_queries(Q, events, rng,
                                            dense=kind == "dense")
                    before = _snapshot(svc)
                    t1 = time.perf_counter()
                    reports = _serve(fe, qs)
                    secs = time.perf_counter() - t1
                    d = _delta(before, _snapshot(svc))
                    c = d["counters"]
                    filt = c["technique"].get("filter", {}).get("launches", 0)
                    if kind == "selective":
                        tree_filter += c["tree_launches"]
                    elif kind == "dense":
                        flat_filter += filt - c["tree_launches"]
                    try:
                        checked += check_against_host(
                            label, reports, [host.run(q) for q in qs])
                    except SmokeFailure as exc:
                        failures.append(str(exc))
                    tech = {t: v["launches"]
                            for t, v in c["technique"].items()}
                    batches.append(dict(batch=label, seconds=secs,
                                        launches=tech,
                                        tree_launches=c["tree_launches"]))
                    log(f"serve: {label}: {secs:.3f}s launches={tech} "
                        f"tree={c['tree_launches']}")
    d = _delta(start, _snapshot(svc))
    c, res = d["counters"], d["resilience"]
    launches = {t: v["launches"] for t, v in c["technique"].items()}
    fallbacks = {t: v["fallbacks"] for t, v in c["technique"].items()}
    summary.update(
        batches=batches, checked_queries=checked,
        launches=dict(launches, filter_flat=flat_filter,
                      filter_tree=tree_filter),
        fallbacks=fallbacks, demotions=res["demotions"],
        passthroughs=res["passthroughs"], errors=res["errors"],
        salvaged_batches=res["salvaged_batches"],
        resident_plane_bytes=int(svc.cache.resident_bytes))
    log(f"counters: launches={json.dumps(summary['launches'])} "
        f"fallbacks={json.dumps(fallbacks)}")
    log(f"counters: demotions={json.dumps(res['demotions'])} "
        f"passthroughs={res['passthroughs']} errors={res['errors']} "
        f"salvaged_batches={res['salvaged_batches']}")
    log(f"checked {checked} reports against the host pipeline; resident "
        f"plane bytes {summary['resident_plane_bytes']}")

    for name in ("filter_flat", "filter_tree", "join", "join_bloom", "topk"):
        if summary["launches"].get(name, 0) <= 0:
            failures.append(f"counters: no {name} launch")
    failures += [f"counters: {t} fell back to the host {n} times"
                 for t, n in fallbacks.items() if n]
    for rung, n in res["demotions"].items():
        if n:
            failures.append(f"counters: {n} demotions into {rung}")
    for key in ("passthroughs", "errors", "salvaged_batches"):
        if res[key]:
            failures.append(f"counters: {key}={res[key]}")
    for rung, exc in svc.ladder.last_errors.items():
        failures.append(
            f"{rung} gave up on: " + "".join(traceback.format_exception(
                type(exc), exc, exc.__traceback__, limit=-3)).strip())
    if failures:
        raise SmokeFailure("\n".join(failures))
    return summary


def run_four_chips(mode: str = "auto", P: int = P_FULL, q_sizes=Q_SIZES,
                   seed: int = SEED, log=print) -> dict:
    """The partition-sharded service on four chips against the unsharded
    service and the host pipeline, bit for bit."""
    import numpy as np

    from repro.core.flow import PruningPipeline
    from repro.serve.frontend import ServingFrontend
    from repro.serve.prune_service import PruningService

    events, users = build_tables(P, seed)
    sharded = PruningService(mode=mode, shard_mesh=True)
    flat = PruningService(mode=mode)
    host = PruningPipeline(filter_mode="host")
    rng = np.random.default_rng(seed + 1)
    failures, checked = [], 0
    for Q in q_sizes:
        with ServingFrontend(sharded, max_batch=Q, deadline_s=3600.0) as fe:
            for kind in ("selective", "dense", "mixed"):
                label = f"Q={Q} {kind}"
                if kind == "mixed":
                    qs = mixed_queries(Q, events, users, rng)
                else:
                    qs = filter_queries(Q, events, rng,
                                        dense=kind == "dense")
                t1 = time.perf_counter()
                got = _serve(fe, qs)
                secs = time.perf_counter() - t1
                ref_reports = flat.run_batch(qs)
                same = sum(reports_identical(a, b)
                           for a, b in zip(got, ref_reports))
                if same != len(qs):
                    failures.append(f"{label}: {len(qs) - same} reports "
                                    "differ from the unsharded service")
                try:
                    checked += check_against_host(
                        label, got, [host.run(q) for q in qs])
                except SmokeFailure as exc:
                    failures.append(str(exc))
                log(f"four-chips: {label}: {secs:.3f}s, {same}/{len(qs)} "
                    "identical to unsharded")
    snap = sharded.fleet_summary()
    c, res = snap["counters"], snap["resilience"]
    summary = dict(P=P, mesh=int(sharded.shard_mesh.devices.size),
                   sharded_launches=c["sharded_launches"],
                   launches=c["launches"], checked_queries=checked,
                   fallbacks=c["host_fallbacks"],
                   demotions=sum(res["demotions"].values()))
    log(f"four-chips: {json.dumps(summary)}")
    if c["sharded_launches"] <= 0:
        failures.append("no sharded launch")
    if c["host_fallbacks"] or summary["demotions"]:
        failures.append(f"fallbacks={c['host_fallbacks']} "
                        f"demotions={res['demotions']}")
    if failures:
        raise SmokeFailure("\n".join(failures))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partition-sharded service on four "
                         "chips, against the unsharded one and the host")
    args = ap.parse_args(argv)
    try:
        _import_engine()
    except ImportError as exc:
        print(f"chip_smoke: the engine is not importable next to this "
              f"script: {exc}", file=sys.stderr)
        return 2
    from repro.compile_cache import use_compile_cache
    import jax

    devs = jax.devices()
    dev = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs))
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev}", file=sys.stderr)
        return 2
    if args.four_chips and dev["count"] < 4:
        print(f"chip_smoke: --four-chips needs 4 chips; found {dev}",
              file=sys.stderr)
        return 2
    print(f"device: {dev['kind']} x{dev['count']} "
          f"(compile cache {use_compile_cache(ROOT)})", flush=True)
    log = lambda s: print(s, flush=True)  # noqa: E731
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            run_four_chips(log=log)
        else:
            run_smoke(log=log)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED\n{exc}", file=sys.stderr)
        return 1
    stats = devs[0].memory_stats() or {}
    print(f"peak device memory: {stats.get('peak_bytes_in_use')} bytes; "
          f"total {time.perf_counter() - t0:.3f}s", flush=True)
    print(json.dumps(dict(ok=True, device=dev)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
