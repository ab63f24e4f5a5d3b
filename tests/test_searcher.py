"""Segment-native read path: vectorized reader build is bit-identical to
the scalar reference; multi-segment search over live segments == exhaustive
search over the force-merged index (== numpy oracle); batched == per-query;
refresh surfaces newly flushed docs without finalizing; the reader cache
only rebuilds new segments across a merge cascade."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.core.indexer import DistributedIndexer
from repro.core.query import bm25_exhaustive
from repro.core.searcher import (IndexSearcher, ReaderCache, SegmentReader,
                                 build_block_index, build_block_index_loop)
from repro.data.corpus import TINY, SyntheticCorpus
from repro.kernels.postings_pack import ref as pack_ref

INDEX_FIELDS = ("terms", "term_block_start", "idf", "packed_docs",
                "bw_docs", "packed_tf", "bw_tf", "first_doc", "max_tf",
                "doc_norm", "min_dl")


def bm25_oracle(tokens, q, k1=0.9, b=0.4):
    D = tokens.shape[0]
    dl = (tokens > 0).sum(1)
    avg = max(dl.mean(), 1.0)
    scores = np.zeros(D)
    for t in set(int(x) for x in q):
        df = int(((tokens == t).any(1)).sum())
        if df == 0:
            continue
        idf = np.log(1 + (D - df + 0.5) / (df + 0.5))
        tf = (tokens == t).sum(1)
        scores += np.where(
            tf > 0, idf * tf * (k1 + 1) / (tf + k1 * (1 - b + b * dl / avg)), 0)
    return scores


@pytest.fixture(scope="module")
def live_index():
    """Indexer fed batch-by-batch (smoke cfg flushes every batch -> real
    multi-segment tier state), plus the concatenated token matrix."""
    cfg = get_arch("lucene-envelope").smoke
    corpus = SyntheticCorpus(TINY, doc_buffer_len=cfg.doc_len)
    ix = DistributedIndexer(cfg=cfg)
    batches = [corpus.batch(i, 32) for i in range(6)]
    for b in batches:
        ix.index_batch(b)
    return ix, np.concatenate(batches)


def test_build_vectorized_bit_identical_to_loop(live_index):
    ix, tokens = live_index
    for seg in ix.merger.live_segments():
        vec, loop = build_block_index(seg), build_block_index_loop(seg)
        for f in INDEX_FIELDS:
            a, b = np.asarray(getattr(vec, f)), np.asarray(getattr(loop, f))
            assert a.dtype == b.dtype and a.shape == b.shape, f
            assert (a == b).all(), f
        assert vec.n_docs == loop.n_docs
        assert vec.max_blocks_per_term == loop.max_blocks_per_term


def test_build_reordered_and_compact_bit_identical(live_index):
    """The builder under the new layouts: on a BP-reordered merge the
    vectorized build still equals the scalar loop field-for-field, and
    the ``compact=True`` build's compressed plane rows expand back to
    exactly the plain build's fixed-stride planes (same blocks, same
    widths — only the storage shape differs)."""
    from repro.core.merge import merge_segments, reassign_doc_ids
    from dataclasses import replace
    ix, tokens = live_index
    m = merge_segments(ix.merger.live_segments())
    seg = replace(m, reorder=reassign_doc_ids(m, min_partition=16))
    assert seg.reorder is not None and not np.array_equal(
        seg.reorder, np.arange(seg.n_docs))
    vec, loop = build_block_index(seg), build_block_index_loop(seg)
    for f in INDEX_FIELDS:
        a, b = np.asarray(getattr(vec, f)), np.asarray(getattr(loop, f))
        assert a.dtype == b.dtype and a.shape == b.shape, f
        assert (a == b).all(), f
    cmp = build_block_index(seg, compact=True)
    assert cmp.compact and cmp.packed_docs is None
    for stream in ("docs", "tf"):
        bw = np.asarray(getattr(vec, f"bw_{stream}"), np.int64)
        words = np.asarray(getattr(cmp, f"cwords_{stream}")).reshape(-1)
        coff = np.asarray(getattr(cmp, f"coff_{stream}"))
        n_words = int(bw.sum()) * pack_ref.WORDS_PER_PLANE
        assert words.size == (n_words // 128 + 2) * 128
        assert not words[n_words:].any()
        assert np.array_equal(coff, np.cumsum(bw) - bw)
        back = pack_ref.expand_planes(words[:n_words], bw)
        want = np.asarray(getattr(vec, f"packed_{stream}")).reshape(
            -1, 32, 4) * (np.arange(32)[None, :, None] < bw[:, None, None])
        assert np.array_equal(back, want)


def test_pack_unpack_fast_match_reference():
    rng = np.random.default_rng(7)
    for hi in (1, 1000, 2 ** 20, 2 ** 32 - 1):
        d = rng.integers(0, hi + 1, (32, 128),
                         dtype=np.uint64).astype(np.uint32)
        d[0] = 0                                  # a bw-0 block
        p_r, bw_r = pack_ref.pack_ref(jnp.asarray(d))
        p_f, bw_f = pack_ref.pack_fast(d)         # the codec's host pack
        assert isinstance(p_f, np.ndarray) and isinstance(bw_f, np.ndarray)
        assert (np.asarray(p_r) == p_f).all()
        assert (np.asarray(bw_r) == bw_f).all()
        u_r = pack_ref.unpack_ref(p_r, bw_r)
        u_f = pack_ref.unpack_fast(p_r, bw_r)     # jnp: the reference path
        u_h = pack_ref.unpack_fast(p_f, bw_f)     # numpy: the codec's decode
        assert isinstance(u_h, np.ndarray)
        assert (np.asarray(u_r) == np.asarray(u_f)).all()
        assert (np.asarray(u_f) == d).all() and (u_h == d).all()


def test_multisegment_equals_forcemerged(live_index):
    ix, tokens = live_index
    from repro.core.merge import merge_segments
    searcher = ix.refresh()
    assert searcher.n_segments > 1, "need live multi-segment tier state"
    # pure union (same content finalize() would produce) — keeps the shared
    # fixture's tier state untouched for the other tests
    merged_idx = build_block_index(merge_segments(ix.merger.live_segments()))
    rng = np.random.default_rng(11)
    vocab = np.unique(tokens[tokens > 0])
    for trial in range(5):
        q = rng.choice(vocab, size=4, replace=False).astype(np.int32)
        v_m, i_m, _ = bm25_exhaustive(merged_idx, jnp.asarray(q), 10)
        v_s, i_s = searcher.search(q, 10)
        np.testing.assert_allclose(np.asarray(v_s), np.asarray(v_m),
                                   rtol=1e-5, atol=1e-6)
        # tie-robust: every returned doc carries its true global score
        oracle = bm25_oracle(tokens, q)
        np.testing.assert_allclose(np.asarray(v_s),
                                   np.sort(oracle)[::-1][:10],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(oracle[np.asarray(i_s)],
                                   np.asarray(v_s), rtol=1e-4, atol=1e-5)


def test_adaptive_block_window_covers_heavy_terms(live_index):
    """A query mixing the heaviest term (multi-block postings) with rare
    ones must still be exact through the narrowed candidate window."""
    ix, tokens = live_index
    searcher = ix.refresh()
    vals, counts = np.unique(tokens[tokens > 0], return_counts=True)
    q = np.array([vals[np.argmax(counts)], vals[np.argmin(counts)],
                  vals[len(vals) // 2]], np.int32)
    assert any(r.query_max_blocks(q) > 1 for r in searcher.readers) or \
        all(r.index.max_blocks_per_term == 1 for r in searcher.readers)
    oracle = bm25_oracle(tokens, q)
    v, i = searcher.search(q, 10)
    np.testing.assert_allclose(np.asarray(v), np.sort(oracle)[::-1][:10],
                               rtol=1e-4, atol=1e-5)


def test_batched_equals_per_query(live_index):
    ix, tokens = live_index
    searcher = ix.refresh()
    rng = np.random.default_rng(13)
    vocab = np.unique(tokens[tokens > 0])
    B, QT = 8, 5
    qb = np.full((B, QT), -1, np.int32)
    lens = rng.integers(1, QT + 1, B)
    for r in range(B):  # ragged queries, right-padded with -1
        qb[r, :lens[r]] = rng.choice(vocab, size=lens[r], replace=False)
    vb, ib = searcher.search_batched(qb, 10)
    for r in range(B):
        v1, i1 = searcher.search(qb[r, :lens[r]], 10)
        np.testing.assert_allclose(np.asarray(vb[r]), np.asarray(v1),
                                   rtol=1e-5, atol=1e-6)
        oracle = bm25_oracle(tokens, qb[r, :lens[r]])
        np.testing.assert_allclose(oracle[np.asarray(ib[r])],
                                   np.asarray(vb[r]), rtol=1e-4, atol=1e-5)


def test_refresh_surfaces_new_docs_without_finalizing():
    cfg = get_arch("lucene-envelope").smoke
    corpus = SyntheticCorpus(TINY, doc_buffer_len=cfg.doc_len)
    ix = DistributedIndexer(cfg=cfg)
    ix.index_batch(corpus.batch(0, 32))
    s1 = ix.refresh()
    assert s1.n_docs == 32
    ix.index_batch(corpus.batch(1, 32))
    assert s1.n_docs == 32  # old snapshot is immutable
    s2 = ix.refresh()
    assert s2.n_docs == 64 and s2.n_segments == 2
    assert ix.merger.n_merges == 0  # no finalize, no forced merge
    # a term of the new batch is retrievable with a doc id from [32, 64)
    b1 = corpus.batch(1, 32)
    q = np.unique(b1[b1 > 0])[:1].astype(np.int32)
    v, ids = s2.search(q, 64)
    hit_docs = np.asarray(ids)[np.asarray(v) > 0]
    assert (hit_docs >= 32).any()


def test_reader_cache_only_rebuilds_new_segments():
    cfg = get_arch("lucene-envelope").smoke  # merge_fanout=4
    corpus = SyntheticCorpus(TINY, doc_buffer_len=cfg.doc_len)
    ix = DistributedIndexer(cfg=cfg)
    for i in range(3):
        ix.index_batch(corpus.batch(i, 32))
    ix.refresh()
    assert ix.reader_cache.builds == 3
    ix.index_batch(corpus.batch(3, 32))  # 4th flush -> cascade merges all 4
    assert ix.merger.n_merges == 1
    ix.refresh()
    # only the cascade output was built; the 3 inputs' readers were evicted
    assert ix.reader_cache.builds == 4
    assert ix.reader_cache.evictions == 3
    hits_before = ix.reader_cache.hits
    ix.refresh()  # nothing changed: pure cache hit, no builds
    assert ix.reader_cache.builds == 4
    assert ix.reader_cache.hits == hits_before + 1


def test_empty_searcher_returns_empty():
    searcher = ReaderCache().refresh([])
    v, i = searcher.search(np.array([5], np.int32), 7)
    assert v.shape == (7,) and (np.asarray(v) == 0).all()
    vb, ib = searcher.search_batched(np.full((3, 2), -1, np.int32), 4)
    assert vb.shape == (3, 4) and (np.asarray(ib) == -1).all()


def test_query_scheduler_matches_direct_search(live_index):
    from repro.serving.query_scheduler import QueryRequest, QueryScheduler
    ix, tokens = live_index
    searcher = ix.refresh()
    rng = np.random.default_rng(17)
    vocab = np.unique(tokens[tokens > 0])
    sched = QueryScheduler(searcher=searcher, slots=4, max_terms=3, k=5)
    reqs = [QueryRequest(rid=i, terms=rng.choice(vocab, size=3,
                                                 replace=False), k=5)
            for i in range(10)]  # more requests than slots
    for r in reqs:
        sched.submit(r)
    done = sched.run_to_completion()
    assert len(done) == 10 and all(r.done for r in reqs)
    assert sched.steps == 3  # 4 + 4 + 2 through fixed-shape batches
    for r in reqs:
        v, i = searcher.search(r.terms, 5)
        np.testing.assert_allclose(r.scores, np.asarray(v), rtol=1e-5,
                                   atol=1e-6)
