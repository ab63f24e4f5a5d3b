"""Whole ingest runs at test size, past the chip check: a sound run is
``correct``; with the write path broken underneath, it is not."""
import numpy as np
import pytest

import bench_tiny


def test_sound_run_is_correct():
    out = bench_tiny.run_small("cw09b.ingest.bulk")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"ingest_docs_per_s", "setup_s"}


def _drop_one_batch(index_batch):
    seen = {}

    def f(self, tokens):
        # each indexer's second batch (the set-up's warm-up indexer has
        # its own): acknowledged, never indexed
        seen[id(self)] = seen.get(id(self), 0) + 1
        if seen[id(self)] == 2:
            return None
        return index_batch(self, tokens)
    return f


def _half_batch(index_batch):
    return lambda self, tokens: index_batch(self, tokens[:len(tokens) // 2])


@pytest.mark.parametrize("fault", [_drop_one_batch, _half_batch],
                         ids=["state_unchanged", "half_batch_left_out"])
def test_broken_ingest_is_not_correct(monkeypatch, fault):
    from repro.core.indexer import DistributedIndexer
    monkeypatch.setattr(DistributedIndexer, "index_batch",
                        fault(DistributedIndexer.index_batch))
    out = bench_tiny.run_small("cw09b.ingest.bulk")
    assert out["correct"] is False, out["checks"]


def test_token_altered_in_a_flush_is_not_correct(monkeypatch):
    from repro.core import indexer
    make = indexer.segment_from_run

    def altered(run, doc_ids, doc_len):
        seg = make(run, doc_ids, doc_len)
        seg.terms[-1] += 1          # still sorted: the last term renamed
        return seg

    monkeypatch.setattr(indexer, "segment_from_run", altered)
    out = bench_tiny.run_small("cw09b.ingest.bulk")
    assert out["correct"] is False
    assert out["checks"]["docs_mismatched"]["value"] > 0
