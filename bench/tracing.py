"""Profiler trace capture and its reduction to numbers.

A traced run records one JAX profiler trace over its measured window,
with the Python tracer off and the benchmark's own host spans
(``TraceAnnotation``, names starting ``bench.``) on.  The reduction reads
the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and nothing else:

* device operations: the events of each accelerator plane's ``XLA Ops``
  line (the per-operation line), as [start, end) intervals;
* busy time: the length of the union of those intervals, per chip,
  averaged over the chips that ran anything;
* a kernel's time: the summed durations of the operations whose name
  holds one of the kernel's names;
* a host activity's time: the summed durations of the host events of
  that name (all host lines are kept);
* idle gaps: the holes between busy intervals inside the window, each
  named by the benchmark span that covers most of it on the host (the
  tighter one on a tie; never the window's own span).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"

Interval = Tuple[float, float]          # [start, end) in ns


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Tuple[str, float, float]]]   # plane -> (name, s, e)
    host: List[Tuple[str, float, float]]             # every host event
    window: Interval                                 # traced window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def spans(self) -> List[Tuple[str, float, float]]:
        """The benchmark's own host spans."""
        return [ev for ev in self.host if ev[0].startswith(SPAN_PREFIX)]


def union_length(intervals: Sequence[Interval]) -> float:
    """Total length covered by a set of intervals (overlaps once)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: Sequence[Tuple[str, float, float]], window: Interval):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in iv
            if e > lo and s < hi]


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over busy chips."""
    per = [union_length([(s, e) for _, s, e in _clip(ops, trace.window)])
           for ops in trace.ops.values()]
    per = [p for p in per if p > 0]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def idle_share(trace: Trace) -> Optional[float]:
    if trace.window_s <= 0 or not trace.ops:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def idle_percent(trace: Optional[Trace]) -> Optional[float]:
    """The idle share in %, or None without a device trace: the one
    reduction behind every ``idle_share.*`` metric."""
    share = None if trace is None else idle_share(trace)
    return None if share is None else 100.0 * share


def host_s(trace: Trace, name: str) -> float:
    """Summed seconds of the host events with this exact name."""
    return sum(e - s for _, s, e in
               _clip([ev for ev in trace.host if ev[0] == name],
                     trace.window)) * 1e-9


def kernel_s(trace: Trace, names: Sequence[str]) -> float:
    """Summed device seconds of operations named like the kernel."""
    tot = 0.0
    for ops in trace.ops.values():
        for n, s, e in _clip(ops, trace.window):
            if any(k in n for k in names):
                tot += e - s
    return tot * 1e-9


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by the host span that covered most of each."""
    by_op: Dict[str, float] = collections.defaultdict(float)
    busy: List[Interval] = []
    for ops in trace.ops.values():
        for n, s, e in _clip(ops, trace.window):
            by_op[n] += (e - s) * 1e-9
            busy.append((s, e))
    gaps, prev = [], trace.window[0]
    for s, e in merged(busy) + [(trace.window[1], trace.window[1])]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, name = None, "no bench span"
        for n, s, e in trace.spans:
            ov = min(e, ge) - max(s, gs)
            if ov > 0 and n != WINDOW_SPAN:
                key = (ov, s - e)          # most overlap, then the tighter
                if best is None or key > best:
                    best, name = key, n
        named.append([name, (ge - gs) * 1e-9])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return dict(device_ops=[[n, v] for n, v in ops], idle_gaps=named)


def op_name(hlo: str) -> str:
    """``%minmax_prune_batched.1 = s32[...] custom-call(...)`` ->
    ``minmax_prune_batched.1``: the trace names each op by its HLO text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path: str, window: Optional[Interval] = None) -> Trace:
    """Read one ``.xplane.pb``; ``window`` defaults to the span named
    ``bench.window`` when the run recorded one."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [(op_name(e.name), e.start_ns, e.end_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    if window is None:
        w = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
        window = w[0] if w else (0.0, 0.0)
    return Trace(ops, host, window)


def describe(path: str, top: int = 12) -> List[str]:
    """Planes, lines and their most frequent event names with their
    summed durations in ms: what a reader is written against."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            dur: Dict[str, float] = collections.defaultdict(float)
            for e in evs:
                dur[e.name[:120]] += e.duration_ns * 1e-6
            names = [(n, c, round(dur[n[:120]], 3)) for n, c in
                     collections.Counter(e.name for e in evs).most_common(top)]
            span = ((min(e.start_ns for e in evs), max(e.end_ns for e in evs))
                    if evs else None)
            out.append(f"{plane.name} | {line.name} | {len(evs)} events "
                       f"| span {span} | {names}")
    return out


class Capture:
    """Start/stop a profiler trace into a scratch directory under
    ``$TMPDIR`` and hand back the reduced ``Trace``; the raw files are
    removed once read."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> Trace:
        import jax
        jax.profiler.stop_trace()
        files = xplanes(self.dir)
        try:
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            return load(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def xplanes(directory: str) -> List[str]:
    return glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)


if __name__ == "__main__":
    # python3 -m bench.tracing <trace directory or .xplane.pb>: the planes,
    # lines and most frequent events of a recorded trace, for writing a
    # reader against.
    import sys
    path = sys.argv[1]
    for row in describe(path if path.endswith(".pb") else xplanes(path)[0]):
        print(row)
