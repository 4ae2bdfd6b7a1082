"""Host stages of the served path, read from the program's own spans.

The program opens a host span named ``prune.<stage>`` at every stage
boundary of its served path (``run_batch``, the techniques, the ladder's
rungs, plane staging, and pack / dispatch / device_wait / d2h / unpack
around every batched launch).  The spans are on the profiler's clock, in
the same trace as the device's operations.  Two reductions read them:

* ``per_batch_ms``: per ``prune.run_batch`` in the traced window, the
  summed milliseconds of the named stages inside it; the median over the
  window's batches.  A closed loop runs one batch at a time on one
  thread; host events carry no thread here, so in an open loop a stage of
  another thread that overlaps a batch would count toward it.
* ``idle_by_stage``: the device-idle seconds of the window, each stretch
  named by the innermost ``prune.*`` span that covers it.

A program without these spans gives ``None`` and ``{}`` respectively.
"""

from __future__ import annotations

import bisect
import collections
import statistics
from typing import Dict, Optional, Sequence

import numpy as np

from . import tracing

PREFIX = "prune."
BATCH = PREFIX + "run_batch"
UNNAMED = "no program span"


def _program_spans(trace: tracing.Trace):
    return [ev for ev in trace.host if ev[0].startswith(PREFIX)]


def per_batch_ms(trace: tracing.Trace, names: Sequence[str]) -> Optional[float]:
    """Median over the window's batches of the summed milliseconds of
    the spans ``prune.<n>`` (``n`` in ``names``) inside each batch's
    ``prune.run_batch``; None when the window holds no batch."""
    lo, hi = trace.window
    spans = _program_spans(trace)
    batches = [(s, e) for n, s, e in spans
               if n == BATCH and s >= lo and e <= hi]
    if not batches:
        return None
    wanted = {PREFIX + n for n in names}
    evs = sorted((s, e) for n, s, e in spans if n in wanted)
    starts = [s for s, _ in evs]
    sums = []
    for bs, be in batches:
        i = bisect.bisect_left(starts, bs)
        sums.append(sum(e - s for s, e in evs[i:bisect.bisect_right(starts, be)]
                        if e <= be))
    return statistics.median(sums) * 1e-6


def idle_gaps(trace: tracing.Trace):
    """The [start, end) holes between device operations in the window."""
    lo, hi = trace.window
    busy = [(max(s, lo), min(e, hi)) for ops in trace.ops.values()
            for _, s, e in ops if e > lo and s < hi]
    gaps, prev = [], lo
    for s, e in tracing.merged(busy) + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def idle_by_stage(trace: tracing.Trace,
                  top: Optional[int] = None) -> Dict[str, float]:
    """Device-idle seconds in the window by the innermost ``prune.*``
    span covering them (the shortest, where spans of several threads
    overlap); ``"no program span"`` for idle time no span covers.
    ``top`` keeps only the longest gaps."""
    gaps = idle_gaps(trace)
    if top is not None:
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    spans = _program_spans(trace)
    name = np.array([n for n, _, _ in spans], dtype=object)
    ss = np.array([s for _, s, _ in spans], dtype=np.float64)
    se = np.array([e for _, _, e in spans], dtype=np.float64)
    out: Dict[str, float] = collections.defaultdict(float)
    for gs, ge in gaps:
        near = (ss < ge) & (se > gs)
        cn, cs, ce = name[near], np.maximum(ss[near], gs), np.minimum(se[near], ge)
        cuts = np.unique(np.concatenate([[gs, ge], cs, ce]))
        for a, b in zip(cuts[:-1], cuts[1:]):
            cover = (cs <= a) & (ce >= b)
            if cover.any():
                dur = (se[near] - ss[near])[cover]
                out[cn[cover][int(np.argmin(dur))]] += (b - a) * 1e-9
            else:
                out[UNNAMED] += (b - a) * 1e-9
    return dict(out)
