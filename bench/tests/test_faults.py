"""The check has to fail a broken timed path.

Each fault is planted under the harness, in the service every window
drives, and the rest of a run goes as usual: ``correct`` has to come out
false.  The cells run on one chip, so the fault of a lost exchange between
chips does not apply.
"""

import numpy as np
import pytest

from bench.tests.common import CELLS, run_small

pytestmark = pytest.mark.filterwarnings("ignore")


def _altered(reports, queries):
    """An answer altered where it is produced: every scan set loses its
    last partition, every top-k its last value."""
    from repro.core.metadata import ScanSet
    for rep in reports:
        for name, ss in list(rep.scan_sets.items()):
            if len(ss):
                rep.scan_sets[name] = ScanSet(ss.part_ids[:-1], ss.match[:-1])
        if rep.topk is not None and len(rep.topk.values):
            rep.topk.values = rep.topk.values[:-1]
    return reports


def _half(reports, queries):
    """Half of the batch left out: the second half gets the first half's
    answers."""
    half = max(len(reports) // 2, 1)
    return [reports[i % half] for i in range(len(reports))]


def _unchanged(reports, queries):
    """A step that leaves its state as it found it: nothing pruned."""
    from repro.core.metadata import PARTIAL_MATCH, ScanSet
    for rep, q in zip(reports, queries):
        for name, spec in q.scans.items():
            P = spec.table.num_partitions
            rep.scan_sets[name] = ScanSet(
                np.arange(P), np.full(P, PARTIAL_MATCH, dtype=np.int8))
    return reports


@pytest.mark.parametrize("fault", [_altered, _half, _unchanged],
                         ids=["answer-altered", "half-batch", "state-unchanged"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch):
    from repro.serve.prune_service import PruningService
    real = PruningService.run_batch
    calls = {"window": False}

    def broken(self, queries, pipeline=None):
        reports = real(self, queries, pipeline)
        return fault(reports, list(queries)) if calls["window"] else reports

    import bench.harness as h
    real_warm = h.warm_up

    def warm_then_break(*a, **kw):
        n = real_warm(*a, **kw)
        calls["window"] = True
        return n

    monkeypatch.setattr(PruningService, "run_batch", broken)
    monkeypatch.setattr(h, "warm_up", warm_then_break)
    r = run_small(cell)
    assert r["correct"] is False
    assert r["checks"]["mismatches"]["value"] > 0
