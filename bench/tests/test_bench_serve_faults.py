"""Whole serving runs at test size, past the chip check: a sound run is
``correct``; with the timed path broken underneath, it is not."""
import numpy as np
import pytest

import bench_tiny


def test_sound_run_is_correct():
    out = bench_tiny.run_small("cw09b.batch.k1000")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"query_p95_ms", "queries_per_s",
                                   "setup_s"}
    assert list(out)[-1] == "checks"


def _answer_altered(vals, ids):
    ids[:, 0] += 1                  # every query's best doc renamed
    return vals, ids


def _half_left_out(vals, ids):
    h = vals.shape[0] // 2
    vals[h:] = 0.0
    ids[h:] = -1
    return vals, ids


class _Stale:
    """Every batch after the first answered with the first one's lists."""

    def __init__(self):
        self.first = None

    def __call__(self, vals, ids):
        if self.first is None or self.first[0].shape != vals.shape:
            self.first = (vals.copy(), ids.copy())
        return self.first[0].copy(), self.first[1].copy()


@pytest.mark.parametrize("fault", [_answer_altered, _half_left_out, _Stale()],
                         ids=["answer_altered", "half_batch_left_out",
                              "state_unchanged"])
def test_broken_serving_is_not_correct(monkeypatch, fault):
    from repro.core.searcher import IndexSearcher
    search = IndexSearcher.search_batched

    def broken(self, q, k=10, theta0=None):
        v, i = search(self, q, k, theta0)
        return fault(np.array(v), np.array(i))

    monkeypatch.setattr(IndexSearcher, "search_batched", broken)
    out = bench_tiny.run_small("cw09b.batch.k1000")
    assert out["correct"] is False, out["checks"]
