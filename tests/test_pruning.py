"""Survivor-proportional pruned serving: the compacted + shared-theta
multi-segment path (tombstones included) must return exactly what
exhaustive evaluation over the force-merged COMPACTED index returns —
values bit-identical, every returned doc carrying its true global score —
while the pruning counters prove the path does survivor-proportional
work. Also covers the scheduler's PruneStats surface and the
cross-segment skip."""
import dataclasses

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs.registry import get_arch
from repro.core.indexer import DistributedIndexer
from repro.core.merge import merge_segments
from repro.core.query import PruneStats, bm25_exhaustive, bm25_topk
from repro.core.searcher import (IndexSearcher, ReaderCache,
                                 build_block_index, evaluator_cache_hits)
from repro.core.segments import Segment
from repro.data.corpus import TINY, SyntheticCorpus
from test_merge import make_segment, tombstoned_seg_set


def _searchers(segs):
    """(pruned searcher, exhaustive searcher over the same readers,
    exhaustive single-index oracle over the force-merged compaction)."""
    pruned = ReaderCache().refresh(segs)
    dense = IndexSearcher(readers=pruned.readers, k1=pruned.k1, b=pruned.b,
                          prune=False)
    midx = build_block_index(merge_segments(list(segs)))
    return pruned, dense, midx


def _query_vocab(segs, rng):
    terms = np.concatenate([s.terms for s in segs] + [np.array([10 ** 6])])
    n = int(rng.integers(1, 5))
    return rng.choice(terms, size=min(n, terms.size),
                      replace=False).astype(np.int32)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 5))
def test_pruned_multisegment_bit_identical_to_forcemerged(seed, n_segs):
    """The tentpole oracle: pruned (compacted + shared theta, with
    deletes) multi-segment top-k == ``bm25_exhaustive`` on the
    force-merged compacted index, bit for bit on values; every returned
    id carries its true global score (ties may legally reorder ids)."""
    segs = tombstoned_seg_set(seed, n_segs)
    n_live = sum(s.live_doc_count for s in segs)
    if n_live == 0:
        return
    pruned, dense, midx = _searchers(segs)
    if int(midx.terms.shape[0]) == 0:
        return  # every live posting tombstoned away: nothing to rank
    rng = np.random.default_rng(seed + 3)
    k = int(min(rng.integers(1, 12), n_live))
    # full exact ranking of the compacted merge -> true score per doc
    v_all, i_all = bm25_exhaustive(midx, jnp.asarray(_q := _query_vocab(
        segs, rng)), midx.n_docs)[:2]
    truth = dict(zip(np.asarray(i_all).tolist(),
                     np.asarray(v_all).tolist()))
    v_e, i_e = np.asarray(v_all)[:k], np.asarray(i_all)[:k]
    # the merged index holds LOCAL ids 0..D-1 == rank in the sorted
    # absolute doc-id space; map to absolute for comparison
    live_ids = np.concatenate([s.live_doc_ids() for s in segs])
    live_ids.sort()
    for searcher in (pruned, dense):
        v_s, i_s = searcher.search(_q, k)
        v_s, i_s = np.asarray(v_s), np.asarray(i_s)
        assert np.array_equal(v_s, v_e), (v_s, v_e)
        for val, doc in zip(v_s, i_s):
            if doc < 0:   # no-hit slot: fewer scoring live docs than k
                assert val == 0
                continue
            li = int(np.searchsorted(live_ids, doc))
            assert li < live_ids.size and live_ids[li] == doc  # live doc
            assert truth[li] == val
    # batched path agrees with the single path
    qb = np.stack([_q, _q])
    vb, ib = pruned.search_batched(qb, k)
    assert np.array_equal(np.asarray(vb)[0], v_e)
    assert np.array_equal(np.asarray(vb)[1], v_e)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_prune_stats_monotone_and_counted(seed):
    segs = [make_segment(np.random.default_rng(seed + i), 1000 * i,
                         n_docs=8) for i in range(3)]
    pruned = ReaderCache().refresh(segs)
    rng = np.random.default_rng(seed)
    q = _query_vocab(segs, rng)
    pruned.search(q, 5)
    st1 = pruned.prune_stats
    assert st1.queries == 1 and st1.batches == 1
    assert 0 <= st1.blocks_survived <= st1.blocks_candidate
    assert st1.blocks_scored >= 0
    pruned.search_batched(np.stack([q, q]), 5)
    assert pruned.prune_stats.queries == 3
    assert pruned.prune_stats.batches == 2


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_reordered_and_compact_serving_bit_identical(seed):
    """The layout-invariance oracle: the same live doc set served off
    (a) the natural-order merge, (b) the BP-reordered merge, and (c) the
    reordered merge through the fused compact (decompress-in-kernel)
    path must return bit-identical score VALUES at every rank, and the
    full (score, doc) ranking must be the same SET — doc order may
    legally differ only among equal scores (top-k tie-breaking follows
    block slot order, which reordering permutes)."""
    from repro.core.merge import reassign_doc_ids
    from dataclasses import replace
    rng = np.random.default_rng(seed)
    segs = tombstoned_seg_set(seed, 3)
    m_nat = merge_segments(list(segs))
    if m_nat.n_docs < 2 or m_nat.n_postings == 0:
        return
    perm = reassign_doc_ids(m_nat, min_partition=2)
    if perm is None:
        perm = rng.permutation(m_nat.n_docs).astype(np.int64)
    m_re = replace(m_nat, reorder=perm)
    # tombstones on top of the reordered layout (delete ~1/4 of docs)
    dead = rng.choice(m_nat.doc_ids, size=m_nat.n_docs // 4, replace=False)
    if dead.size:
        m_nat, m_re = m_nat.with_deletes(dead), m_re.with_deletes(dead)
    n_live = m_nat.live_doc_count
    if n_live == 0:
        return
    q = _query_vocab([m_nat], rng)
    k = n_live  # full ranking: makes the set comparison total
    outs = []
    for compact, seg in ((False, m_nat), (False, m_re), (True, m_re)):
        s = ReaderCache(compact=compact).refresh([seg])
        v, i = s.search(q, k)
        outs.append((np.asarray(v), np.asarray(i)))
    (v0, i0), (v1, i1), (v2, i2) = outs
    assert np.array_equal(v0, v1) and np.array_equal(v0, v2)
    ranked = {tuple(np.sort(ix[vx > 0]).tolist()) for vx, ix in outs}
    assert len(ranked) == 1, "hit sets diverged across layouts"
    for vx, ix in outs[1:]:
        assert sorted(zip(v0.tolist(), i0.tolist())) \
            == sorted(zip(vx.tolist(), ix.tolist()))


def _disjoint_range_segment(seed, n_big=4, n_small=2, span=2000):
    """Synthetic segment where each term's postings cover a PRIVATE doc
    range (term t lives in [t*span, (t+1)*span)): the BMW range-overlap
    bound sees zero cross-term help while term-level MaxScore must assume
    full help everywhere. The last ``n_small`` terms get < 128 postings
    (single-block terms)."""
    rng = np.random.default_rng(seed)
    n_terms = n_big + n_small
    N = n_terms * span
    doc_len = rng.integers(5, 30, N).astype(np.int64)
    docs, tf, term_start = [], [], [0]
    for t in range(n_terms):
        m = int(rng.integers(20, 100)) if t >= n_big else span // 2
        ds = t * span + np.sort(rng.choice(span, size=m, replace=False))
        docs.extend(ds.tolist())
        tf.extend(rng.integers(1, 8, m).tolist())
        term_start.append(len(docs))
    tf = np.asarray(tf, np.int64)
    pos_start = np.concatenate([[0], np.cumsum(tf)])
    positions = np.concatenate([np.arange(c) for c in tf])
    return Segment(terms=np.arange(n_terms, dtype=np.int64),
                   term_start=np.asarray(term_start, np.int64),
                   docs=np.asarray(docs, np.int64), tf=tf,
                   positions=positions, pos_start=pos_start,
                   doc_ids=np.arange(N, dtype=np.int64), doc_len=doc_len)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_bmw_balanced_disjunction_exact_and_strictly_cheaper(seed):
    """Balanced disjunctions over disjoint per-term doc ranges: BMW must
    stay bit-identical to exhaustive (values AND ids) while scoring
    strictly fewer blocks than term-level MaxScore — the tentpole's
    measurable claim. Single-block terms ride along in every query."""
    seg = _disjoint_range_segment(seed)
    bmw_s = ReaderCache().refresh([seg])
    ms_s = ReaderCache(bmw=False, midgrid=False).refresh([seg])
    midx = build_block_index(merge_segments([seg]))
    rng = np.random.default_rng(seed + 7)
    big = rng.choice(4, size=3, replace=False)
    q = np.concatenate([big, [4 + rng.integers(0, 2)]]).astype(np.int32)
    k = 10
    v_e, i_e = bm25_exhaustive(midx, jnp.asarray(q), k)[:2]
    v_b, i_b = bmw_s.search(q, k)
    v_m, i_m = ms_s.search(q, k)
    assert np.array_equal(np.asarray(v_b), np.asarray(v_e))
    assert np.array_equal(np.asarray(i_b), np.asarray(i_e))
    assert np.array_equal(np.asarray(v_m), np.asarray(v_e))
    assert np.array_equal(np.asarray(i_m), np.asarray(i_e))
    assert bmw_s.prune_stats.blocks_scored \
        < ms_s.prune_stats.blocks_scored, \
        (bmw_s.prune_stats, ms_s.prune_stats)
    assert bmw_s.prune_stats.terms_eliminated > 0
    assert ms_s.prune_stats.terms_eliminated == 0


def test_bmw_all_nonessential_query_returns_nothing_safely():
    """An external ``theta0`` no term combination can reach makes EVERY
    term non-essential: candidate generation collapses, nothing is
    scored above theta0, and the securing contract means returning no
    positive scores is exact."""
    seg = _disjoint_range_segment(3)
    midx = build_block_index(merge_segments([seg]))
    q = jnp.asarray(np.array([0, 1, 2, 3], np.int32))
    v, i, stt = bm25_topk(midx, q, 10, theta0=1e9)
    assert (np.asarray(v) == 0).all()
    ps = stt["prune_stats"]
    assert ps.terms_eliminated == 4
    assert stt["blocks_survived"] == 0


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_bmw_theta0_with_tombstones_keeps_strictly_above(seed):
    """Cross-shard securing contract under BMW + tombstones: with an
    externally-seeded theta0, every result STRICTLY above it must match
    the exact ranking over the same snapshot, bit for bit."""
    segs = tombstoned_seg_set(seed, 3)
    if sum(s.live_doc_count for s in segs) == 0:
        return
    pruned, dense, midx = _searchers(segs)
    if int(midx.terms.shape[0]) == 0:
        return
    rng = np.random.default_rng(seed + 5)
    q = _query_vocab(segs, rng)
    k = int(min(6, sum(s.live_doc_count for s in segs)))
    v_e, i_e = dense.search(q, k)
    v_e, i_e = np.asarray(v_e), np.asarray(i_e)
    theta0 = float(v_e[k // 2])
    v_t, i_t = pruned.search_batched(np.asarray(q)[None], k, theta0=theta0)
    above = v_e > theta0
    assert np.array_equal(np.asarray(v_t)[0][above], v_e[above])
    assert np.array_equal(np.asarray(i_t)[0][above], i_e[above])


def test_evaluator_cache_shared_across_same_shaped_readers():
    """Satellite: compiled evaluators are keyed on shapes + statics, not
    reader identity — a fresh reader over the same-shaped segment reuses
    the already-compiled fns and the hit counter (surfaced through
    ``envelope_report``) moves."""
    seg = make_segment(np.random.default_rng(77), 0, n_docs=8)
    q = _query_vocab([seg], np.random.default_rng(1))
    ReaderCache().refresh([seg]).search(q, 5)
    before = evaluator_cache_hits()
    ReaderCache().refresh([seg]).search(q, 5)   # fresh readers, same shapes
    assert evaluator_cache_hits() > before


def test_cross_segment_skip_preserves_results():
    """A segment whose best possible score cannot beat the shared theta
    is skipped without being evaluated — and results stay exact. Build
    one segment with high-tf postings for a term and another where the
    same term only appears at tf=1 in longer docs (strictly lower
    bound)."""
    rng = np.random.default_rng(0)
    strong = make_segment(rng, 0, n_docs=8, max_tf=6, one_term=True)
    weak = make_segment(rng, 1000, n_docs=8, one_term=True,
                        single_postings=True)  # tf=1 everywhere
    weak.doc_len[:] = 200  # long docs: every score strictly lower
    segs = [strong, weak]
    pruned, dense, midx = _searchers(segs)
    q = np.array([7], np.int32)  # the one_term vocabulary
    k = 4
    v_p, i_p = pruned.search(q, k)
    v_d, i_d = dense.search(q, k)
    assert np.array_equal(np.asarray(v_p), np.asarray(v_d))
    st1 = pruned.prune_stats
    if float(np.asarray(v_p)[k - 1]) > 0:
        # skip only engages when theta beats the weak segment's bound;
        # with tf=1 vs tf>=1 it should here
        assert st1.segments_visited + st1.segments_skipped == 2


def test_query_scheduler_prune_stats_survive_swap():
    from repro.serving.query_scheduler import QueryRequest, QueryScheduler
    cfg = get_arch("lucene-envelope").smoke
    corpus = SyntheticCorpus(TINY, doc_buffer_len=cfg.doc_len)
    ix = DistributedIndexer(cfg=cfg)
    ix.index_batch(corpus.batch(0, 32))
    s1 = ix.refresh()
    sched = QueryScheduler(searcher=s1, slots=4, max_terms=3, k=5)
    b0 = corpus.batch(0, 32)
    vocab = np.unique(b0[b0 > 0])
    rng = np.random.default_rng(11)
    for i in range(6):
        sched.submit(QueryRequest(rid=i, k=5,
                                  terms=rng.choice(vocab, 3,
                                                   replace=False)))
    sched.run_to_completion()
    st1 = sched.prune_stats
    # 6 requests through 4 slots: one full batch + one 2-wide pow2 bucket
    assert st1.batches == 2 and st1.queries == 4 + 2
    assert st1.blocks_scored > 0
    # a searcher swap must not lose the served counters
    ix.index_batch(corpus.batch(1, 32))
    sched.swap_searcher(ix.refresh())
    assert sched.prune_stats.batches == st1.batches
    sched.submit(QueryRequest(rid=99, k=5, terms=vocab[:2]))
    sched.run_to_completion()
    st2 = sched.prune_stats
    assert st2.batches == st1.batches + 1
    assert st2.blocks_scored >= st1.blocks_scored
    # envelope_report surfaces the searcher-level counters
    rep = ix.envelope_report()
    for key in ("blocks_candidate", "blocks_survived", "blocks_scored",
                "segments_skipped", "prune_skip_rate", "terms_eliminated",
                "blocks_skipped_midgrid", "evaluator_cache_hits"):
        assert key in rep
    ix.close()


def test_pruned_search_under_churn_via_indexer():
    """End-to-end: index, churn (deletes + re-adds), refresh — pruned
    results equal the exhaustive searcher over the same snapshot AND the
    finalized compacted index, with tombstoned docs never returned."""
    cfg = dataclasses.replace(get_arch("lucene-envelope").smoke)
    corpus = SyntheticCorpus(TINY, doc_buffer_len=cfg.doc_len)
    ix = DistributedIndexer(cfg=cfg)
    for i in range(4):
        ix.index_batch(corpus.batch(i, 32))
    ix.delete(np.arange(0, 40))          # tombstone across segments
    ix.index_batch(corpus.batch(4, 32))  # re-add fresh docs
    s = ix.refresh()
    dense = IndexSearcher(readers=s.readers, k1=s.k1, b=s.b, prune=False)
    b0 = corpus.batch(0, 32)
    vocab = np.unique(b0[b0 > 0])
    rng = np.random.default_rng(23)
    deleted = set(range(40))
    for _ in range(5):
        q = rng.choice(vocab, 4, replace=False).astype(np.int32)
        v_p, i_p = s.search(q, 10)
        v_d, i_d = dense.search(q, 10)
        assert np.array_equal(np.asarray(v_p), np.asarray(v_d))
        hit = np.asarray(i_p)[np.asarray(v_p) > 0]
        assert not (set(hit.tolist()) & deleted)
