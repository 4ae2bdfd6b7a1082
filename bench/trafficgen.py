"""The one traffic generator: reads a mix file and draws plain queries.

A mix (``bench/traffic/<mix>.json``) is data only:

* ``loop``: ``{"kind": "open", "rate_qps", "max_batch", "deadline_s"}``
  or ``{"kind": "closed", "batch"}``;
* ``cycle``: template names, repeated to fill the traffic, so every seed
  sends the same count of each kind; ``order`` says how the seed orders
  them: ``"shuffle"`` (the whole open-loop arrival list, or each closed
  batch), ``"cycle"`` (positions kept) or ``"streams"`` (each pass over
  the cycle is one seeded permutation, as a TPC-H query stream);
* ``templates``: per name, ``params`` drawn in order, then ``scans``
  (``{table: predicate}``), and optional ``join``, ``limit``, ``order_by``;
* ``warmup`` and ``check``: batch sizes to warm up, and how many finished
  requests the correctness check samples.

A predicate is a nested list: ``["and", p, ...]``, ``["or", p, ...]``,
``["cmp", col, op, v]``, ``["colcmp", col, op, col2]``, ``["in", col, vs]``
or ``["true"]``.  A value ``"$name"`` refers to a drawn parameter.
The plain query this returns is a dict of plain values; ``bench.program``
turns it into the engine's query and ``bench.reference`` evaluates it.
"""

from __future__ import annotations

import datetime
from typing import Iterator, List

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream): any whole seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _days(d: datetime.date) -> int:
    return (d - EPOCH).days


def _date(days: int) -> datetime.date:
    return EPOCH + datetime.timedelta(days=int(days))


def _add_months(days: int, n: int) -> int:
    d = _date(days)
    m = d.month - 1 + n
    return _days(datetime.date(d.year + m // 12, m % 12 + 1, d.day))


def draw_param(spec: dict, env: dict, rng: np.random.Generator):
    (kind, arg), = ((k, v) for k, v in spec.items() if k != "max")
    if kind == "uniform":
        return float(rng.uniform(arg[0], arg[1]))
    if kind == "randint":                     # [lo, hi)
        return int(rng.integers(arg[0], arg[1]))
    if kind == "lognormal":                   # [median, sigma]
        v = float(np.exp(rng.normal(np.log(arg[0]), arg[1])))
        return min(v, spec["max"]) if "max" in spec else v
    if kind == "choose":                      # [values, n] distinct
        idx = rng.choice(len(arg[0]), size=arg[1], replace=False)
        return [arg[0][int(i)] for i in sorted(idx)]
    if kind == "year":                        # Jan 1 of a year, inclusive
        return _days(datetime.date(int(rng.integers(arg[0], arg[1] + 1)), 1, 1))
    if kind == "month":                       # first of a month, inclusive
        (y0, m0), (y1, m1) = arg
        k = int(rng.integers(0, (y1 - y0) * 12 + m1 - m0 + 1))
        return _add_months(_days(datetime.date(y0, m0, 1)), k)
    if kind == "day_in":                      # a day, inclusive range
        lo, hi = (_days(datetime.date.fromisoformat(s)) for s in arg)
        return int(rng.integers(lo, hi + 1))
    if kind == "add_months":
        return _add_months(env[arg[0]], arg[1])
    if kind == "add":
        return env[arg[0]] + arg[1]
    if kind == "lin":                         # a + b * param
        return arg[0] + arg[1] * env[arg[2]]
    raise ValueError(f"unknown parameter kind {kind!r}")


def _value(v, env: dict):
    if isinstance(v, str) and v.startswith("$"):
        return env[v[1:]]
    if isinstance(v, list):
        return [_value(x, env) for x in v]
    return v


def bind(pred: list, env: dict) -> list:
    """Substitute drawn parameters into a predicate tree."""
    op = pred[0]
    if op in ("and", "or"):
        return [op] + [bind(p, env) for p in pred[1:]]
    if op == "cmp":
        return ["cmp", pred[1], pred[2], _value(pred[3], env)]
    if op == "in":
        vals = _value(pred[2], env)
        return ["in", pred[1], list(vals)]
    if op in ("colcmp", "true"):
        return list(pred)
    raise ValueError(f"unknown predicate node {op!r}")


def draw_query(name: str, tmpl: dict, rng: np.random.Generator) -> dict:
    env: dict = {}
    for pname, pspec in tmpl.get("params", {}).items():
        env[pname] = draw_param(pspec, env, rng)
    limit = tmpl.get("limit")
    return dict(
        kind=name,
        scans={t: bind(p, env) for t, p in tmpl["scans"].items()},
        join=tmpl.get("join"),
        limit=None if limit is None else int(_value(limit, env)),
        order_by=tmpl.get("order_by"))


class Traffic:
    """Seeded plain-query source for one mix."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = int(seed)
        self.loop = mix["loop"]
        self.cycle: List[str] = list(mix["cycle"])
        self.templates = mix["templates"]

    def _kinds(self, n: int, rng) -> List[str]:
        order = self.mix.get("order", "cycle")
        if order == "streams":
            out: List[str] = []
            while len(out) < n:
                out += [self.cycle[int(i)]
                        for i in rng.permutation(len(self.cycle))]
            return out[:n]
        kinds = [self.cycle[i % len(self.cycle)] for i in range(n)]
        if order == "shuffle":
            kinds = [kinds[int(i)] for i in rng.permutation(n)]
        return kinds

    def queries(self, n: int, stream: int) -> List[dict]:
        """``n`` queries of one stream (batch index, or an arrival list)."""
        rng = rng_for(self.seed, 1, stream)
        return [draw_query(k, self.templates[k], rng)
                for k in self._kinds(n, rng)]

    def batches(self, size: int) -> Iterator[List[dict]]:
        """Closed loop: batch ``b`` is stream ``b``."""
        b = 0
        while True:
            yield self.queries(size, b)
            b += 1

    def arrivals(self, seconds: float) -> np.ndarray:
        """Open loop: ``rate x seconds`` due times, uniform order
        statistics on [0, seconds) — a Poisson process conditioned on its
        count, so every seed offers the same number of requests."""
        n = max(1, int(round(self.loop["rate_qps"] * seconds)))
        return np.sort(rng_for(self.seed, 2).uniform(0.0, seconds, n))
