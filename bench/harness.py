"""One run of one cell: set up, warm up, measure, check, report.

``run_cell`` does no device check and takes the kernel ``mode``, so the
CPU tests drive every other step of a run with it; ``bench.run`` is the
command, which refuses to run without the chip.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import gc
import json
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from . import check, program, spec, tracing
from .reference import Reference
from .trafficgen import Traffic, rng_for

WARMUP_STREAM = 1_000_000     # warm-up traffic never repeats the window's
LATE_WAIT_S = 60.0            # how long past the close a request may finish
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Service calls a traced run wraps in host spans, so that the breakdown can
# say which stage the host was in during each idle gap of the device.
TRACED_METHODS = ("run_batch", "prestage", "prune_batch", "join_hit_batch",
                  "bloom_hit_batch", "topk_init_batch")


T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f} s] {msg}", file=sys.stderr,
          flush=True)


@dataclasses.dataclass
class RunData:
    """What a run measured: the input of every metric reader."""

    window_s: float
    requests: List[dict]            # open loop: one per arrival
    batches: List[dict]             # one per run_batch call in the window
    trace: Optional[tracing.Trace]
    peaks: Optional[dict]


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.n = 0
        self.names: List[str] = []

        def on_event(event, _secs, fun_name="?", **_kw):
            if event == COMPILE_EVENT:
                self.n += 1
                self.names.append(fun_name)
        jax.monitoring.register_event_duration_secs_listener(on_event)


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(tracing.SPAN_PREFIX + name)


def _annotate(svc, method: str):
    """Wrap a service method, on this instance only, in a host span."""
    import jax
    inner = getattr(svc, method)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(tracing.SPAN_PREFIX + method):
            return inner(*a, **kw)
    setattr(svc, method, wrapped)


def _batch_shape(queries: List[dict], tables_data: dict) -> Dict[str, dict]:
    """Per scanned table: how many queries filter it and on how many
    distinct columns (what a filter launch must read and write)."""
    out: Dict[str, dict] = {}
    for q in queries:
        for t, p in q["scans"].items():
            if p[0] == "true":
                continue
            d = out.setdefault(t, dict(q=0, cols=set(),
                                       P=tables_data[t].num_partitions))
            d["q"] += 1
            d["cols"] |= _columns(p)
    return {t: dict(q=d["q"], cols=len(d["cols"]), P=d["P"])
            for t, d in out.items()}


def _columns(pred: list) -> set:
    if pred[0] in ("and", "or"):
        return set().union(*(_columns(p) for p in pred[1:]))
    if pred[0] == "colcmp":
        return {pred[1], pred[3]}
    if pred[0] in ("cmp", "in"):
        return {pred[1]}
    return set()


def closed_loop(svc, traffic: Traffic, tables, tables_data, seconds: float,
                keep: int):
    """Back-to-back ``run_batch`` until ``seconds`` have passed; keeps the
    reports of ``keep`` positions per batch, drawn from the seed."""
    size = traffic.loop["batch"]
    batches, kept = [], []
    before = program.snapshot(svc)
    t0 = time.perf_counter()
    for b, qs in enumerate(traffic.batches(size)):
        if time.perf_counter() - t0 >= seconds:
            break
        eq = [program.to_query(q, tables) for q in qs]
        c0 = svc.counters.snapshot()
        ts = time.perf_counter()
        reports = svc.run_batch(eq)
        te = time.perf_counter()
        c1 = svc.counters.snapshot()
        flat = ((c1["technique"].get("filter", {}).get("launches", 0)
                 - c0["technique"].get("filter", {}).get("launches", 0))
                - (c1["tree_launches"] - c0["tree_launches"]))
        batches.append(dict(n=len(qs), t_launch=ts, t_done=te,
                            tables=_batch_shape(qs, tables_data),
                            flat_filter_launches=flat,
                            failed=sum(program.failed(r) for r in reports)))
        pos = rng_for(traffic.seed, 3, b).choice(len(qs), size=min(keep, len(qs)),
                                                 replace=False)
        kept += [(qs[i], program.outcome(reports[i])) for i in sorted(pos)]
        del reports, eq
    t_end = time.perf_counter()
    done = sum(b["n"] for b in batches)
    return dict(window_s=t_end - t0, t0=t0, attempted=done, completed=done,
                failed=sum(b["failed"] for b in batches), batches=batches,
                kept=kept, requests=[], counters=program.counter_delta(svc, before))


def open_loop(svc, traffic: Traffic, tables, seconds: float, traced: bool):
    """Poisson arrivals through the threaded front end; each request is
    timed from its due time, and the window closes ``seconds`` after the
    first was due."""
    from repro.serve.frontend import ServingFrontend
    loop = traffic.loop
    due = traffic.arrivals(seconds)
    qs = traffic.queries(len(due), stream=0)
    eq = [program.to_query(q, tables) for q in qs]
    fe = ServingFrontend(svc, max_batch=loop["max_batch"],
                         deadline_s=loop["deadline_s"], threaded=True,
                         prefetch=True)
    before = program.snapshot(svc)
    futs, late = [], []
    t0 = time.monotonic()
    with _span("generator", traced):
        for d, q in zip(due, eq):
            wait = t0 + d - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            late.append(time.monotonic() - (t0 + d))
            with _span("submit", traced):
                futs.append(fe.submit(q))
        t_close = t0 + seconds
        if t_close > time.monotonic():
            time.sleep(t_close - time.monotonic())
    concurrent.futures.wait(futs, timeout=max(
        0.0, t_close + LATE_WAIT_S - time.monotonic()))
    requests, kept = [], []
    for d, q, f in zip(due, qs, futs):
        r = dict(due=t0 + d, done=None, queue_ms=None, latency_ms=None,
                 launched=None, failed=True)
        if f.done() and f.exception() is None:
            resp = f.result()
            r.update(done=resp.timestamps["done"], queue_ms=resp.queue_ms,
                     launched=resp.timestamps["launched"],
                     latency_ms=(resp.timestamps["done"] - (t0 + d)) * 1e3,
                     failed=program.failed(resp.report))
            kept.append((q, program.outcome(resp.report)))
        else:
            kept.append((q, None))
        requests.append(r)
    if all(f.done() for f in futs):
        fe.close()
    batches = {}
    for r in requests:
        if r["launched"] is not None:
            batches[(r["launched"], r["done"])] = dict(
                t_launch=r["launched"], t_done=r["done"])
    lat = sorted(late)
    log(f"generator: {len(due)} arrivals at {loop['rate_qps']} queries/s; "
        f"lateness p50 {lat[len(lat) // 2] * 1e3:.3f} ms, "
        f"max {lat[-1] * 1e3:.3f} ms")
    return dict(window_s=seconds, t0=t0, t_close=t_close,
                attempted=len(due),
                completed=sum(1 for r in requests
                              if r["done"] is not None and r["done"] <= t_close),
                failed=sum(r["failed"] for r in requests),
                batches=list(batches.values()), kept=kept, requests=requests,
                counters=program.counter_delta(svc, before))


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def end_to_end(out: dict, setup_s: float) -> Dict[str, float]:
    m = dict(qps=out["completed"] / out["window_s"], setup_s=setup_s)
    if out["requests"]:
        cap = out["t_close"] + LATE_WAIT_S
        lat = [r["latency_ms"] if r["latency_ms"] is not None
               else (cap - r["due"]) * 1e3 for r in out["requests"]]
        m["p50_ms"] = percentile(lat, 50)
        m["p95_ms"] = percentile(lat, 95)
    return m


def verify(tables_data: dict, kept: list, sample_n: int, seed: int,
           dtype=np.float64) -> dict:
    """Compare a seeded sample of the finished requests' outcomes
    (``kept``: plain query, ``Outcome`` or None) with the plain reference
    in ``dtype``; returns counts and the first differences."""
    ref = Reference(tables_data, dtype=dtype)
    done = [(q, rep) for q, rep in kept if rep is not None]
    idx = check.sample(len(done), sample_n, rng_for(seed, 4))
    mismatches, notes = 0, []
    for i in idx:
        q, rep = done[i]
        diff = check.differences(rep, ref.outcome(q), ref, q)
        if diff:
            mismatches += 1
            if len(notes) < 5:
                notes.append(f"{q['kind']}: " + "; ".join(diff))
    return dict(checked=len(idx), mismatches=mismatches, notes=notes)


def warm_up(svc, traffic: Traffic, tables, warm: dict) -> int:
    """Stage the planes and compile the shapes the window will use: one
    ``run_batch`` for each size in the mix's fixed warm-up list, drawn from
    the cell's own traffic on streams the window never uses.  The sizes
    cover the window's query-count buckets; the list is the same for every
    seed, so every run does the same warm-up work."""
    sizes = warm.get("batches", [traffic.loop.get("batch", 64)])
    for i, size in enumerate(sizes):
        svc.run_batch([program.to_query(q, tables)
                       for q in traffic.queries(size, WARMUP_STREAM + i)])
    return len(sizes)


class GcClock:
    """Counts the interpreter's garbage collections and their seconds."""

    def __init__(self):
        self.n, self.s, self._t = 0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.s += time.perf_counter() - self._t
            self._t = None

    def stop(self):
        gc.callbacks.remove(self._on)
        return self.n, self.s


def host_summary(batches: List[dict], gc_n: int, gc_s: float) -> str:
    """Per-batch times, collections and peak host memory of a window:
    what a spread between runs is read against."""
    import resource
    d = np.sort([b["t_done"] - b["t_launch"] for b in batches])
    q = (np.percentile(d, [10, 50, 90]) if d.size else [0.0] * 3)
    return (f"window: {d.size} batches, seconds p10 {q[0]:.4f} p50 {q[1]:.4f} "
            f"p90 {q[2]:.4f} max {d[-1] if d.size else 0.0:.4f}; gc {gc_n} "
            f"collections in {gc_s:.4f} s; host max RSS "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss >> 10} MiB")


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, mode: str = "auto", t_start: float,
             overrides: Optional[dict] = None) -> dict:
    import jax

    from repro.serve.prune_service import PruningService
    global T0
    T0 = t_start
    bench = spec.Benchmark(root)
    cell = bench.workload(workload)
    cfg = dict(bench.config(cell["config"]), **(overrides or {}))
    mix = bench.traffic(cell["traffic"])
    counter = CompileCounter()

    t = time.perf_counter()
    tables_data = spec.generator(cfg["generator"]).generate(cfg, rng_for(seed, 0))
    tables = {n: program.build_table(td) for n, td in tables_data.items()}
    log(f"setup: generated {', '.join(f'{n} P={td.num_partitions} rows={td.num_rows}' for n, td in tables_data.items())} "
        f"in {time.perf_counter() - t:.3f} s")

    svc = PruningService(mode=mode, verdict_cache=cfg["verdict_cache"])
    traffic = Traffic(mix, seed)
    t = time.perf_counter()
    n_warm = warm_up(svc, traffic, tables, mix.get("warmup", {}))
    log(f"setup: staged and warmed up in {time.perf_counter() - t:.3f} s "
        f"({n_warm} batches, {counter.n} programs compiled or loaded; "
        f"resident planes {svc.cache.resident_bytes} B)")

    cap = tracing.Capture() if trace else None
    if trace:
        for method in TRACED_METHODS:
            _annotate(svc, method)
        cap.start()
    setup_s = time.perf_counter() - t_start
    n0 = counter.n
    gcc = GcClock()
    with _span("window", trace):
        if traffic.loop["kind"] == "open":
            out = open_loop(svc, traffic, tables, seconds, trace)
        else:
            out = closed_loop(svc, traffic, tables, tables_data, seconds,
                              mix["check"].get("per_batch", 4))
    compiles = counter.n - n0
    gc_n, gc_s = gcc.stop()
    tr = cap.stop() if trace else None
    log(host_summary(out["batches"], gc_n, gc_s))
    log(f"window: {compiles} programs compiled or loaded inside the window"
        + (f" ({', '.join(counter.names[n0:][:8])})" if compiles else ""))
    c, res = out["counters"]["counters"], out["counters"]["resilience"]
    log(f"window: launches {json.dumps({k: v['launches'] for k, v in c['technique'].items()})}, "
        f"tree {c['tree_launches']}; fallbacks "
        f"{json.dumps({k: v['fallbacks'] for k, v in c['technique'].items()})}; "
        f"demotions {json.dumps(res['demotions'])}; passthroughs "
        f"{res['passthroughs']}, errors {res['errors']}, salvaged "
        f"{res['salvaged_batches']}")

    devs = jax.devices()
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs),
                  memory_peak_bytes=int(max((d.memory_stats() or {}).get(
                      "peak_bytes_in_use", 0) for d in devs)))
    if tr is not None:
        device["busy_s"] = tracing.busy_s(tr)
        device["window_s"] = tr.window_s

    if trace:
        from .peaks import peaks
        run = RunData(out["window_s"], out["requests"],
                      out["batches"], tr,
                      peaks(device["kind"]) if device["platform"] == "tpu"
                      else None)
        metrics = {}
        for m in bench.metrics(workload, "per_layer"):
            v = bench.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        e2e = end_to_end(out, setup_s)
        metrics = {m["name"]: dict(value=e2e[m["name"]], unit=m["unit"])
                   for m in bench.metrics(workload, "end_to_end")
                   if m["name"] in e2e}

    kept = out["kept"]
    del svc, tables, out["requests"], out["batches"]
    gc.collect()
    t = time.perf_counter()
    ver = verify(tables_data, kept, mix["check"]["sample"], seed)
    log(f"check: {ver['checked']} requests against the reference in "
        f"{time.perf_counter() - t:.3f} s")
    for n in ver["notes"]:
        log(f"check: {n}")
    correct = ver["mismatches"] <= check.MISMATCH_LIMIT and ver["checked"] > 0
    result = dict(correct=correct, attempted=out["attempted"],
                  failed=out["failed"], metrics=metrics, device=device)
    if tr is not None:
        result["breakdown"] = tracing.breakdown(tr)
    result["checks"] = dict(
        mismatches=dict(value=ver["mismatches"], limit=check.MISMATCH_LIMIT),
        checked=dict(value=ver["checked"], limit=1))
    log(f"checked {ver['checked']} (at least 1)")
    log(f"mismatches {ver['mismatches']} (limit {check.MISMATCH_LIMIT})")
    return result

