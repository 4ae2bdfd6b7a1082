"""Plain reference of the pruning outcome (Pruning in Snowflake, Sec. 3-6).

For each plain query it works out, from the generated rows alone:

* the filter scan set of every scan: each partition's min/max per column,
  and the predicate on those intervals under the three-valued lattice
  (NO < PARTIAL < FULL; AND takes the least, OR the greatest);
* the LIMIT cut of a plain ``LIMIT k`` (no ORDER BY, no join): the fewest
  fully-matching partitions whose rows reach k, larger partitions first
  and then earlier ones; short of k, the fully-matching partitions are
  moved to the front instead;
* the join-pruned probe scan: the build side's keys are the matching rows
  of its scan set; a probe partition stays only if its key range meets
  the keys' range and, for at most ``NDV_LIMIT`` distinct keys, holds one
  of them, or, past that, if it is wider than ``ENUM_LIMIT`` values or one
  of its whole values is in the build side's blocked Bloom filter
  (512-bit blocks, 4 probes, 16 bits per key, murmur3 finalizer);
* top-k (ORDER BY c LIMIT k, no join): the true k best values of the
  matching rows, and the partitions a boundary scan in block-max order
  skips: those that cannot beat the k-th value of the rows read so far,
  or the boundary that the fully-matching partitions' own stats prove.

Literals of a categorical column are ranks in its sorted dictionary; one
that is not in the dictionary sits half a rank below where it would go,
so comparisons keep their order and equality never holds.

``dtype`` is the precision every value and literal is rounded to before
it is compared: float64 is the reference; a lower one is the control.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

NO, PARTIAL, FULL = 0, 1, 2
NDV_LIMIT = 4096         # most distinct build keys kept as an exact list
BITS_PER_KEY = 16
ENUM_LIMIT = 1024        # widest key range enumerated against the filter
BLOCK_WORDS = 16         # 32-bit words per 512-bit block
K_PROBES = 4


@dataclasses.dataclass
class Outcome:
    """What one query's pruning produced: per scan the ordered partition
    ids and their match values, and for top-k the values and skips."""

    scan_sets: Dict[str, Tuple[np.ndarray, np.ndarray]]
    topk_values: Optional[np.ndarray] = None
    topk_skipped: Optional[np.ndarray] = None


def _fmix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def _bloom_coords(keys: np.ndarray, n_blocks: int):
    k = keys.astype(np.int64)
    lo = (k & np.int64(0xFFFFFFFF)).astype(np.uint32)
    hi = ((k >> np.int64(32)) & np.int64(0xFFFFFFFF)).astype(np.uint32)
    h0 = _fmix32(lo ^ _fmix32(hi))
    h1 = _fmix32(h0 ^ np.uint32(0x9E3779B9))
    h2 = _fmix32(h1 ^ np.uint32(0x7F4A7C15))
    base = (h0 & np.uint32(n_blocks - 1)).astype(np.int64) * BLOCK_WORDS
    words = [base + ((h1 >> np.uint32(8 * i)) & np.uint32(15)).astype(np.int64)
             for i in range(K_PROBES)]
    bits = [(h2 >> np.uint32(8 * i)) & np.uint32(31) for i in range(K_PROBES)]
    return words, bits


class Bloom:
    def __init__(self, keys: np.ndarray):
        n_blocks = 1
        while n_blocks * BLOCK_WORDS * 32 < max(len(keys), 1) * BITS_PER_KEY:
            n_blocks *= 2
        self.n_blocks = n_blocks
        self.words = np.zeros(n_blocks * BLOCK_WORDS, dtype=np.uint32)
        for w, b in zip(*_bloom_coords(keys, n_blocks)):
            np.bitwise_or.at(self.words, w, np.uint32(1) << b)

    def contains(self, keys: np.ndarray) -> np.ndarray:
        ok = np.ones(len(keys), dtype=bool)
        for w, b in zip(*_bloom_coords(keys, self.n_blocks)):
            ok &= ((self.words[w] >> b) & np.uint32(1)) == 1
        return ok


def _cmp3(op: str, amin, amax, bmin, bmax) -> np.ndarray:
    """Three-valued ``a <op> b`` of interval a against interval b."""
    if op == ">":
        full, no = amin > bmax, amax <= bmin
    elif op == ">=":
        full, no = amin >= bmax, amax < bmin
    elif op == "<":
        full, no = amax < bmin, amin >= bmax
    elif op == "<=":
        full, no = amax <= bmin, amin > bmax
    elif op == "==":
        no = (amax < bmin) | (amin > bmax)
        full = (amin == amax) & (bmin == bmax) & (amin == bmin)
    else:
        raise ValueError(f"unsupported comparison {op!r}")
    tv = np.where(no, NO, np.where(full, FULL, PARTIAL))
    return np.where((amin > amax) | (bmin > bmax), NO, tv).astype(np.int8)


def _row_cmp(op: str, a, b) -> np.ndarray:
    return {">": np.greater, ">=": np.greater_equal, "<": np.less,
            "<=": np.less_equal, "==": np.equal}[op](a, b)


class Reference:
    def __init__(self, tables: dict, dtype=np.float64):
        self.tables = tables
        self.dtype = np.dtype(dtype)
        self._vals: dict = {}
        self._stats: dict = {}

    # -- values in the reference's precision --------------------------------

    def _round(self, x):
        if self.dtype == np.float64:
            return np.asarray(x, dtype=np.float64)
        return np.asarray(x).astype(self.dtype).astype(np.float64)

    def values(self, t: str, c: str) -> np.ndarray:
        if (t, c) not in self._vals:
            self._vals[t, c] = self._round(self.tables[t].columns[c])
        return self._vals[t, c]

    def stats(self, t: str, c: str):
        if (t, c) not in self._stats:
            starts = self.tables[t].part_bounds[:-1]
            v = self.values(t, c)
            self._stats[t, c] = (np.minimum.reduceat(v, starts),
                                 np.maximum.reduceat(v, starts))
        return self._stats[t, c]

    def literal(self, t: str, c: str, v) -> float:
        tb = self.tables[t]
        if tb.kinds[c] == "str":
            d = tb.dictionaries[c]
            i = int(np.searchsorted(d, v))
            v = i if i < len(d) and d[i] == v else i - 0.5
        return float(self._round(float(v)))

    def rows_of(self, t: str, ids: np.ndarray) -> np.ndarray:
        r = self.tables[t].rows_per_partition
        rows = (np.asarray(ids, dtype=np.int64)[:, None] * r
                + np.arange(r)[None, :]).ravel()
        return rows[rows < self.tables[t].num_rows]

    # -- predicates -------------------------------------------------------

    def tv(self, t: str, pred: list) -> np.ndarray:
        op = pred[0]
        P = self.tables[t].num_partitions
        if op == "true":
            return np.full(P, FULL, dtype=np.int8)
        if op in ("and", "or"):
            parts = [self.tv(t, p) for p in pred[1:]]
            f = np.minimum if op == "and" else np.maximum
            out = parts[0]
            for p in parts[1:]:
                out = f(out, p)
            return out.astype(np.int8)
        if op == "cmp":
            _, c, o, v = pred
            mn, mx = self.stats(t, c)
            lit = self.literal(t, c, v)
            return _cmp3(o, mn, mx, lit, lit)
        if op == "colcmp":
            _, a, o, b = pred
            return _cmp3(o, *self.stats(t, a), *self.stats(t, b))
        if op == "in":
            _, c, vs = pred
            mn, mx = self.stats(t, c)
            lits = np.sort([self.literal(t, c, v) for v in vs])
            hit = (np.searchsorted(lits, mx, side="right")
                   > np.searchsorted(lits, mn, side="left"))
            tv = np.where(hit, np.where(mn == mx, FULL, PARTIAL), NO)
            return np.where(mn > mx, NO, tv).astype(np.int8)
        raise ValueError(f"unknown predicate node {op!r}")

    def rows_match(self, t: str, pred: list, rows) -> np.ndarray:
        op = pred[0]
        if op == "true":
            n = len(self.values(t, next(iter(self.tables[t].columns)))[rows])
            return np.ones(n, dtype=bool)
        if op in ("and", "or"):
            ms = [self.rows_match(t, p, rows) for p in pred[1:]]
            return (np.logical_and if op == "and" else np.logical_or).reduce(ms)
        if op == "cmp":
            _, c, o, v = pred
            return _row_cmp(o, self.values(t, c)[rows], self.literal(t, c, v))
        if op == "colcmp":
            _, a, o, b = pred
            return _row_cmp(o, self.values(t, a)[rows], self.values(t, b)[rows])
        if op == "in":
            _, c, vs = pred
            return np.isin(self.values(t, c)[rows],
                           [self.literal(t, c, v) for v in vs])
        raise ValueError(f"unknown predicate node {op!r}")

    # -- techniques -------------------------------------------------------

    def filter_set(self, t: str, pred: list):
        tv = self.tv(t, pred)
        ids = np.nonzero(tv > NO)[0].astype(np.int64)
        return ids, tv[ids]

    def limit_cut(self, t: str, ids, match, k: int):
        if k == 0:
            return ids[:0], match[:0]
        if len(ids) <= 1:
            return ids, match
        rows = np.diff(self.tables[t].part_bounds)[ids]
        full = match == FULL
        if not full.any() or rows[full].sum() < k:
            order = np.argsort(~full, kind="stable")
            return ids[order], match[order]
        fidx = np.nonzero(full)[0]
        fidx = fidx[np.argsort(-rows[fidx], kind="stable")]
        need = int(np.searchsorted(np.cumsum(rows[fidx]), k)) + 1
        keep = np.sort(fidx[:need])
        return ids[keep], match[keep]

    def join_probe(self, q: dict, sets: dict):
        j = q["join"]
        b, p = j["build"], j["probe"]
        rows = self.rows_of(b, sets[b][0])
        keys = self.values(b, j["build_key"])[rows][
            self.rows_match(b, q["scans"][b], rows)]
        ids, match = sets[p]
        if keys.size == 0:
            return ids[:0], match[:0]
        uniq = np.unique(keys)
        mn, mx = self.stats(p, j["probe_key"])
        pmin, pmax = mn[ids], mx[ids]
        keep = (pmax >= uniq[0]) & (pmin <= uniq[-1]) & ~(pmin > pmax)
        if uniq.size <= NDV_LIMIT:
            keep &= (np.searchsorted(uniq, pmax, side="right")
                     > np.searchsorted(uniq, pmin, side="left"))
        elif self.tables[p].kinds[j["probe_key"]] != "float":
            width = pmax - pmin + 1.0
            narrow = np.nonzero(keep & (width > 0) & (width <= ENUM_LIMIT))[0]
            if narrow.size:
                bloom = Bloom(uniq)
                w = width[narrow].astype(np.int64)
                cand = (pmin[narrow].astype(np.int64)[:, None]
                        + np.arange(ENUM_LIMIT)[None, :])
                valid = np.arange(ENUM_LIMIT)[None, :] < w[:, None]
                hit = bloom.contains(cand.ravel()).reshape(cand.shape)
                keep[narrow[~(hit & valid).any(axis=1)]] = False
        return ids[keep], match[keep]

    def topk(self, q: dict, sets: dict):
        t, c, desc = q["order_by"]
        k = int(q["limit"])
        sign = 1.0 if desc else -1.0
        pred = q["scans"][t]
        vals = self.values(t, c)
        matching = sign * vals[self.rows_match(t, pred, slice(None))]
        best = np.sort(matching)[::-1][:k]

        ids, match = sets[t]
        mn, mx = self.stats(t, c)
        bmax = (mx if desc else -mn)[ids]
        order = np.argsort(-bmax, kind="stable")
        ids, match, bmax = ids[order], match[order], bmax[order]
        bound = self._proved_bound(t, c, ids, match, k, sign)
        skip = bmax < bound
        heap = np.empty(0)
        for pos in np.nonzero(~skip)[0]:
            kth = heap[k - 1] if len(heap) >= k else -np.inf
            if bmax[pos] < max(bound, kth) or (len(heap) >= k
                                               and bmax[pos] <= kth):
                skip[pos] = True
                continue
            rows = self.rows_of(t, ids[pos:pos + 1])
            new = sign * vals[rows][self.rows_match(t, pred, rows)]
            heap = np.sort(np.concatenate([heap, new]))[::-1][:k]
        return sign * best, ids[skip]

    def _proved_bound(self, t, c, ids, match, k, sign) -> float:
        """The k-th best value the fully-matching partitions' stats alone
        guarantee (each holds a row at its max, and all its rows are at
        least its min); -inf when they guarantee none."""
        full = ids[match == FULL]
        if k <= 0 or full.size == 0:
            return -np.inf
        mn, mx = self.stats(t, c)
        hi = (mx if sign > 0 else -mn)[full]
        lo = (mn if sign > 0 else -mx)[full]
        rows = np.diff(self.tables[t].part_bounds)[full]
        hi, lo, rows = hi[rows > 0], lo[rows > 0], rows[rows > 0]
        if hi.size == 0:
            return -np.inf
        a = float(np.sort(hi)[-k]) if hi.size >= k else -np.inf
        order = np.argsort(-lo, kind="stable")
        pos = int(np.searchsorted(np.cumsum(rows[order]), k))
        b = float(lo[order][pos]) if pos < lo.size else -np.inf
        return max(a, b)

    def unsound_skips(self, q: dict, skipped: np.ndarray) -> int:
        """Skipped partitions holding a matching row strictly better than
        the true k-th value: each one would change the top-k result."""
        t, c, desc = q["order_by"]
        k = int(q["limit"])
        sign = 1.0 if desc else -1.0
        pred = q["scans"][t]
        vals = sign * self.values(t, c)
        best = np.sort(vals[self.rows_match(t, pred, slice(None))])[::-1]
        kth = best[k - 1] if len(best) >= k else -np.inf
        bad = 0
        for pid in np.asarray(skipped, dtype=np.int64):
            rows = self.rows_of(t, np.array([pid]))
            m = self.rows_match(t, pred, rows)
            bad += bool((vals[rows][m] > kth).any())
        return bad

    # -- a whole query ------------------------------------------------------

    def outcome(self, q: dict) -> Outcome:
        sets = {t: self.filter_set(t, p) for t, p in q["scans"].items()}
        if q["limit"] is not None and q["order_by"] is None and not q["join"]:
            sets = {t: self.limit_cut(t, *s, q["limit"])
                    for t, s in sets.items()}
        if q["join"]:
            sets[q["join"]["probe"]] = self.join_probe(q, sets)
        out = Outcome(sets)
        if q["limit"] is not None and q["order_by"] is not None:
            if q["join"]:
                raise NotImplementedError("top-k behind a join")
            out.topk_values, out.topk_skipped = self.topk(q, sets)
        return out
