"""The plain references against the system at a tiny size, and the
controls: the same comparisons a run makes must pass the program and
fail the lower-precision reference (serving) and the program's own
commit-without-flush path (ingest)."""
import numpy as np
import pytest

import bench_tiny
from lib import manifest as mf
from lib import traffic
from lib.corpus import Corpus
from lib.harness import _merge
from lib.reference import (Fingerprints, TokenCollection,
                           compare_fingerprints, compare_topk)


@pytest.fixture(scope="module")
def served():
    """A tiny cw09b collection, its searcher, and 48 queries per k."""
    from repro.configs.base import EnvelopeConfig
    from repro.core.indexer import DistributedIndexer
    m = mf.load()
    cfg = _merge(mf.config_file(m, "cw09b"), bench_tiny.SMALL_CONFIG)
    corpus = Corpus(cfg["corpus"], bench_tiny.SEED)
    per = cfg["index"]["docs_per_shard"]
    batches = corpus.batches(0, cfg["n_docs"] // per, per)
    ix = DistributedIndexer(cfg=EnvelopeConfig(**cfg["index"]))
    for b in batches:
        ix.index_batch(b)
    searcher = ix.refresh()
    ix.close()
    assert len(searcher.readers) > 1      # cross-segment merge exercised
    mix = mf.traffic_file("batch.k1000")
    qs = traffic.queries(mix, corpus, 48, np.random.default_rng(7))
    return cfg, batches, searcher, qs


@pytest.mark.parametrize("k", [10, 100])
def test_bm25_reference_matches_the_searcher(served, k):
    from lib.serve import LIMITS
    cfg, batches, searcher, qs = served
    q2d = np.full((len(qs), 8), -1, np.int32)
    for i, q in enumerate(qs):
        q2d[i, :len(q)] = q
    vals, ids = (np.asarray(a) for a in searcher.search_batched(q2d, k))
    coll = TokenCollection(batches)
    post = coll.postings(np.concatenate(qs), (1 << 14) - 1)
    bm = cfg["bm25"]
    gaps = [compare_topk(vals[i], ids[i],
                         *coll.bm25_scores(q, post, bm["k1"], bm["b"]), k)
            for i, q in enumerate(qs)]
    assert max(g[0] for g in gaps) < LIMITS["value_gap"] / 10
    assert max(g[1] for g in gaps) < LIMITS["id_gap"] / 10


@pytest.mark.parametrize("workload", ["cw09b.batch.k1000"])
def test_serving_control_fails_the_limits(workload):
    """bfloat16 in the program's place reads above a limit on every
    seed tried; float64 against itself reads 0."""
    from control import serve_control
    from lib.serve import LIMITS
    m = mf.load()
    cell = mf.cell(m, workload)
    cfg = _merge(mf.config_file(m, cell["config"]), bench_tiny.SMALL_CONFIG)
    mix = _merge(mf.traffic_file(cell["traffic"]),
                 bench_tiny.SMALL_TRAFFIC[workload])
    for seed in (bench_tiny.SEED, 11, 12):
        got = serve_control(cfg, mix, seed, 2.0)
        assert got["value_gap"] > LIMITS["value_gap"], got
        same = serve_control(cfg, mix, seed, 2.0, dtype=np.float64)
        assert same == {"value_gap": 0.0, "id_gap": 0.0}


def test_inversion_reference_matches_recovery(tmp_path):
    from repro.configs.base import EnvelopeConfig
    from repro.core.indexer import DistributedIndexer
    from repro.storage import FSDirectory, open_latest
    m = mf.load()
    cfg = _merge(mf.config_file(m, "cw09b"), bench_tiny.SMALL_CONFIG)
    corpus = Corpus(cfg["corpus"], 3)
    batches = corpus.batches(0, 6, 512)
    ix = DistributedIndexer(cfg=EnvelopeConfig(**cfg["index"]),
                            target_dir=FSDirectory(str(tmp_path)))
    ref = Fingerprints(0, 6 * 512)
    for i, b in enumerate(batches):
        ix.index_batch(b)
        ref.add_tokens(b, i * 512)
        if i % 2:
            ix.commit()
    ix.close()
    got = Fingerprints(0, 6 * 512)
    _, segs = open_latest(FSDirectory(str(tmp_path)))
    extra = sum(got.merge(Fingerprints.of_segment(s)) for s in segs)
    assert compare_fingerprints(ref, got, extra) == {
        "docs_missing": 0, "docs_mismatched": 0}
    # one posting's tf off by one is seen
    s = segs[0]
    s.tf[len(s.tf) // 2] += 1
    bad = Fingerprints(0, 6 * 512)
    extra = sum(bad.merge(Fingerprints.of_segment(x)) for x in segs)
    assert compare_fingerprints(ref, bad, extra)["docs_mismatched"] == 1


def test_ingest_control_fails_the_limits():
    """At the configuration's own 256 MB flush budget, which a test-size
    group never fills; a zero-second window feeds exactly one group."""
    from control import ingest_control
    cfg = _merge(bench_tiny.SMALL_CONFIG, {"index": {"flush_budget_mb": 256}})
    res = ingest_control("cw09b.ingest.bulk", bench_tiny.SEED, 0.0,
                         check_chip=False,
                         overrides={"config": cfg,
                                    "traffic": bench_tiny.SMALL_TRAFFIC[
                                        "cw09b.ingest.bulk"]})
    assert res["correct"] is False
    assert res["checks"]["docs_missing"]["value"] == res["attempted"] > 0
