"""The comparison that decides ``correct``.

A checked request matches when every scan set equals the reference's,
partition ids in order and their FULL/PARTIAL marks, and, for top-k, the
values are the true k best, every partition the reference's boundary scan
skips is skipped too, and no skipped partition holds a row better than the
k-th value.  The number compared is the count of checked requests that do
not match; it is exact, so its limit is 0.  The rules are those of
``check_against_host`` in ``chip_smoke.py`` (commit 29576fc), with the
program's host pipeline replaced by the plain reference.
"""

from __future__ import annotations

from typing import List

import numpy as np

MISMATCH_LIMIT = 0


def differences(got, want, ref, q: dict) -> List[str]:
    out = []
    if set(got.scan_sets) != set(want.scan_sets):
        return [f"scans {sorted(got.scan_sets)} != {sorted(want.scan_sets)}"]
    for name, (ids, match) in want.scan_sets.items():
        gids, gmatch = got.scan_sets[name]
        if not np.array_equal(gids, ids):
            out.append(f"{name}: {len(gids)} partitions, reference {len(ids)}")
        elif not np.array_equal(gmatch, match):
            out.append(f"{name}: FULL/PARTIAL marks differ on "
                       f"{int((gmatch != match).sum())} partitions")
    if (got.topk_values is None) != (want.topk_values is None):
        out.append("top-k present on one side only")
    elif want.topk_values is not None:
        if not np.array_equal(got.topk_values, want.topk_values):
            out.append("top-k values differ")
        missing = np.setdiff1d(want.topk_skipped, got.topk_skipped)
        if missing.size:
            out.append(f"top-k scanned {missing.size} partitions the "
                       "boundary rules out")
        bad = ref.unsound_skips(q, got.topk_skipped)
        if bad:
            out.append(f"top-k skipped {bad} partitions holding better rows")
    return out


def sample(n_done: int, k: int, rng: np.random.Generator) -> np.ndarray:
    """Indices of ``k`` finished requests drawn from the seed, always
    with the last one, in order."""
    if n_done <= k:
        return np.arange(n_done)
    idx = rng.choice(n_done - 1, size=k - 1, replace=False)
    return np.sort(np.append(idx, n_done - 1))
