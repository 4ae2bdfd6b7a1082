"""Corpus curation over the pruner: work stealing across loader workers,
and the pruned data pipeline's yield and deterministic replay."""

import numpy as np

from repro.core import expr as E
from repro.data.pipeline import (PrunedDataLoader, WorkQueue, curate,
                                 make_corpus_metadata)


class TestWorkStealing:
    def test_all_shards_processed_exactly_once(self):
        q = WorkQueue(np.arange(37), n_workers=4)
        seen = []
        # worker 3 is a straggler: never asks for work after its first item
        order = [0, 1, 2, 3] + [0, 1, 2] * 20
        for w in order:
            sid = q.next_for(w)
            if sid is not None:
                seen.append(sid)
        assert sorted(seen) == list(range(37))

    def test_fast_workers_steal_from_straggler(self):
        q = WorkQueue(np.arange(40), n_workers=2)
        done_by_0 = []
        for _ in range(35):
            sid = q.next_for(0)
            if sid is None:
                break
            done_by_0.append(sid)
        # worker 0 did its 20 plus stole from worker 1's tail
        assert len(done_by_0) > 20

    def test_queue_state_roundtrip(self):
        q = WorkQueue(np.arange(10), n_workers=2)
        for _ in range(3):
            q.next_for(0)
        st = q.state()
        q2 = WorkQueue(np.arange(10), n_workers=2)
        q2.restore(st)
        assert q2.next_for(0) == q.next_for(0)


class TestPrunedPipeline:
    def test_curation_prunes_and_loader_yields(self):
        rng = np.random.default_rng(0)
        meta = make_corpus_metadata(rng, n_shards=128, docs_per_shard=8)
        pred = E.col("quality") >= 0.5
        scan, report = curate(meta, pred)
        assert 0.1 < report.pruning_ratio < 0.9
        loader = PrunedDataLoader(scan, worker=0, n_workers=1, batch_size=2,
                                  seq_len=64, vocab=1000)
        batches = list(iter(loader))
        assert len(batches) > 10
        assert batches[0]["tokens"].shape == (2, 64)
        assert (batches[0]["tokens"] < 1000).all()

    def test_deterministic_replay(self):
        rng = np.random.default_rng(1)
        meta = make_corpus_metadata(rng, n_shards=64, docs_per_shard=8)
        scan, _ = curate(meta, E.col("quality") >= 0.3)
        mk = lambda: PrunedDataLoader(scan, 0, 1, 2, 32, 500, seed=7)
        a = [b["tokens"] for b in mk()]
        b = [b["tokens"] for b in mk()]
        assert len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
