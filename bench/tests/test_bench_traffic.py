"""The traffic generator: query lengths in a mix's shares, and the same
work for every seed (the same queries by corpus rank, relabelled and
reordered)."""
import numpy as np
import pytest

import bench_tiny
from lib import manifest as mf
from lib import traffic
from lib.corpus import Corpus
from lib.harness import _merge


def _corpus(seed):
    cfg = _merge(mf.config_file(mf.load(), "cw09b"), bench_tiny.SMALL_CONFIG)
    return Corpus(cfg["corpus"], seed)


@pytest.mark.parametrize("n", [1, 7, 200, 1001])
def test_query_sizes_follow_the_shares(n):
    mix = mf.traffic_file("batch.k1000")
    sizes = traffic.query_sizes(mix, n, np.random.default_rng(0))
    assert sizes.size == n
    shares = mix["term_shares"]
    total = sum(shares.values())
    for length, share in shares.items():
        assert abs((sizes == int(length)).sum() - n * share / total) < 1


def test_every_seed_asks_the_same_queries_by_rank():
    mix = mf.traffic_file("batch.k1000")
    seen = []
    for seed in (bench_tiny.SEED, 5):
        corpus = _corpus(seed)
        rank = np.argsort(corpus.rank_to_term)      # term id - 1 -> rank
        qs = traffic.queries(mix, corpus, 200, np.random.default_rng(seed))
        seen.append((qs, sorted(tuple(sorted(rank[q - 1])) for q in qs)))
    (qa, ra), (qb, rb) = seen
    assert ra == rb
    assert [list(q) for q in qa] != [list(q) for q in qb]


def test_window_and_warm_up_streams_differ():
    mix = mf.traffic_file("batch.k1000")
    corpus = _corpus(bench_tiny.SEED)
    rng = np.random.default_rng
    win = traffic.queries(mix, corpus, 200, rng(1), traffic.WINDOW)
    warm = traffic.queries(mix, corpus, 200, rng(1), traffic.WARM)
    assert {tuple(q) for q in win} != {tuple(q) for q in warm}
