"""BM25 query evaluation over the block-max index.

The paper positions inverted indexes + block-max WAND as the retrieval
standard; this is the serving path over the indexes the pipeline builds.
Layout: per term, postings padded to 128-lane blocks (Lucene 8's block-max
granularity); per block: first/last doc id, max tf, packed doc deltas and
tfs (lane-blocked PFor). Query evaluation is two-phase, TPU-idiomatic BMW:

  phase 1  score a small set of highest-upper-bound candidate blocks,
           take the running k-th best score as a (valid) threshold theta;
  phase 2  a block of term t is skipped iff
           UB(block) + sum_{t' != t} UB_max(t') <= theta  (MaxScore test —
           a doc scoring in that block cannot reach theta even with
           maximal help from every other query term);
  finally  score surviving blocks exactly; the result equals exhaustive
           evaluation (tests/test_query.py asserts this).

Two implementations share that contract:

``bm25_topk_dense``  the original fully-jittable evaluation: every
    candidate lane is computed and the pruning decision only *masks*
    eliminated blocks, so FLOPs and memory traffic stay O(candidate
    blocks) no matter how many blocks the bounds eliminate. Retained as
    the parity oracle (and as the exhaustive path via ``prune=False``).

``bm25_topk``        the production pruned path: a cheap jittable
    *metadata* pass (``prune_candidates`` — per-block upper bounds, no
    postings decode) feeds the bound test (``prune_decide``, jitted, on
    the metadata's device arrays), the surviving block ids are
    **compacted** on the device (gathered into a dense array, padded to a
    power-of-two bucket so compiled shapes stay bounded), and only the
    compacted blocks are decoded + scored (``score_survivors``). Cost is
    proportional to *surviving* blocks — the first serving path that is
    actually cheaper than exhaustive on the hardware we run (CPU included;
    on TPU the compacted scorer dispatches to the Pallas skip kernel).

Index *construction* lives in ``core/searcher.py`` (``build_block_index``
plus the per-segment ``SegmentReader`` / multi-segment ``IndexSearcher``
machinery); this module only holds the device-resident index layout, the
scoring math and the pruning protocol. Scoring accepts optional ``idf_q``
/ ``doc_norm`` overrides so a multi-segment searcher can evaluate each
segment under *global* collection statistics — which is what makes
per-segment top-k merge bit-equal to searching the force-merged index —
and ``theta0`` seeds the threshold from OUTSIDE the segment, so a
searcher can thread the running global k-th score across segments
(cross-segment threshold sharing: later segments prune harder).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bm25_blockmax.ops import (bm25_blocks, bm25_blocks_compact,
                                             bm25_blocks_midgrid)
from repro.kernels.postings_pack import ops as pack_ops
from repro.spans import span

BLOCK = 128
# phase-1 budget: blocks scored to establish theta. One 128-lane block
# already yields >= k candidate docs for serving k's, so a small constant
# suffices — and it keeps phase-1 cost O(1) instead of O(candidates)/2.
PHASE1_BLOCKS = 8
# survivor buckets: compacted arrays are padded to the next power of two,
# never below this floor, so each (k, bucket) pair compiles at most once
# and the number of distinct buckets is log2-bounded.
MIN_BUCKET = 8
# midgrid theta tightening runs the skip kernel with a SHORT grid step so
# the running k-th-best carry gets a chance to bite within one survivor
# bucket (with the serving default of 128 rows/step most buckets are a
# single step and the carry never feeds back).
MIDGRID_BLOCK_ROWS = 8
# the in-kernel k-th-best fold unrolls k-1 max/mask rounds per step;
# beyond this k the unroll cost outweighs the skipped blocks.
MIDGRID_MAX_K = 32


@dataclass
class BlockMaxIndex:
    """Device-resident block-max positional-free scoring index."""

    terms: jnp.ndarray            # (T,) sorted
    term_block_start: jnp.ndarray  # (T+1,) CSR into blocks
    idf: jnp.ndarray              # (T,) segment-local idf
    packed_docs: jnp.ndarray      # (NB, 128) words, lane 4*plane + word
    bw_docs: jnp.ndarray          # (NB,)
    packed_tf: jnp.ndarray        # (NB, 128)
    bw_tf: jnp.ndarray            # (NB,)
    first_doc: jnp.ndarray        # (NB,) local (remapped) doc ids
    max_tf: jnp.ndarray           # (NB,)
    doc_norm: jnp.ndarray         # (D,) k1*(1-b+b*dl/avgdl), segment-local
    n_docs: int
    max_blocks_per_term: int
    k1: float = 0.9
    b: float = 0.4
    # per-block competitive impact metadata (Lucene's impacts shape): the
    # shortest doc length in each block. Together with ``max_tf`` it
    # majorizes every (tf, norm) pair the block holds, so upper bounds
    # use the block's best REACHABLE norm instead of the global dl=0
    # floor — dramatically tighter on length-varying corpora. None on
    # indexes built before this field existed (bounds fall back to dl=0).
    min_dl: jnp.ndarray = None    # (NB,)
    avgdl: float = 1.0            # segment-local mean live doc length
    # per-block doc-id EXTENT: the last (largest) local doc id the block
    # holds. Together with ``first_doc`` it gives each block's doc-id
    # range [first, last] — within a term, blocks are doc-sorted with
    # disjoint ranges, which is what lets the BMW overlap bound replace
    # the global per-term "others" sum with the sum over blocks whose
    # ranges actually intersect (see ``pruned_eval``). None on indexes
    # built before this field existed (bounds fall back to term-level).
    last_doc: jnp.ndarray = None  # (NB,)
    # COMPACT storage layout (fused decompress-and-score): instead of the
    # fixed-stride buffers above, keep only the live bit-plane words — the
    # exact bytes the storage codec writes, in 128-word rows
    # (``postings_pack.ref.compact_words``) — plus per-block row offsets;
    # selected blocks are expanded inside the scoring computation (Pallas
    # grid on TPU, jnp gather on CPU). When set, ``packed_docs``/
    # ``packed_tf`` are None: the decoded form is never device-resident.
    cwords_docs: jnp.ndarray = None   # (~sum(bw_docs) / 32 + 2, 128)
    coff_docs: jnp.ndarray = None     # (NB,) first plane row of each block
    cwords_tf: jnp.ndarray = None     # (~sum(bw_tf) / 32 + 2, 128)
    coff_tf: jnp.ndarray = None       # (NB,)

    @property
    def compact(self) -> bool:
        return self.cwords_docs is not None

    def packed_bytes(self) -> float:
        return float(pack_ops.packed_bytes(self.bw_docs)
                     + pack_ops.packed_bytes(self.bw_tf))


@dataclass
class PruneStats:
    """Serving-side pruning counters, accumulated per evaluation batch.

    ``blocks_candidate``  lanes the query *could* touch (the dense path's
                          cost); ``blocks_survived`` blocks that passed
                          the MaxScore test; ``blocks_scored`` blocks the
                          compacted path actually decoded + scored
                          (phase-1 probes + bucket-padded survivors — the
                          real FLOP count, padding included).
    ``segments_skipped``  segments eliminated wholesale because their
                          best possible score could not beat the shared
                          theta (cross-segment threshold sharing).
    ``terms_eliminated``  per-(query, segment) non-essential terms whose
                          cumulative best contribution could not reach
                          theta — dropped from candidate generation, only
                          probed for overlap bounds (BMW).
    ``blocks_skipped_midgrid``  compacted survivor blocks zeroed by the
                          kernel's in-grid theta tightening: their stored
                          full-score UB fell below the running k-th-best
                          lower bound folded from earlier grid steps.
    ``blocks_margin_kept``  candidate blocks that pass the float32 bound
                          test only through its rounding slack
                          (``bound * (1 + slack) > theta >= bound``): the
                          work the device test's safety margin costs.
    """

    queries: int = 0
    batches: int = 0
    segments_visited: int = 0
    segments_skipped: int = 0
    blocks_candidate: int = 0
    blocks_survived: int = 0
    blocks_scored: int = 0
    terms_eliminated: int = 0
    blocks_skipped_midgrid: int = 0
    blocks_margin_kept: int = 0

    def add(self, other: "PruneStats") -> None:
        for f in self.__dataclass_fields__:
            setattr(self, f, getattr(self, f) + getattr(other, f))

    def snapshot(self) -> "PruneStats":
        return PruneStats(**{f: getattr(self, f) for f in
                             self.__dataclass_fields__})

    def delta(self, prev: "PruneStats") -> "PruneStats":
        return PruneStats(**{f: getattr(self, f) - getattr(prev, f)
                             for f in self.__dataclass_fields__})

    @property
    def skip_rate(self) -> float:
        """Fraction of candidate blocks NOT scored by the compacted path.
        NEGATIVE for tiny candidate sets: ``blocks_scored`` includes the
        phase-1 probe and the bucket-padding floor, a fixed overhead that
        can exceed a short query's few candidate blocks — an honest
        signal that pruning only pays once candidates outnumber it."""
        if self.blocks_candidate == 0:
            return 0.0
        return 1.0 - self.blocks_scored / self.blocks_candidate


def _gather_term_blocks(index: BlockMaxIndex, q_terms, max_blocks=None):
    """For each query term: row lookup + padded block-id window.

    ``max_blocks`` narrows the window below the segment-wide
    ``max_blocks_per_term``; callers must guarantee every *query* term has
    at most that many blocks (the searcher computes the exact per-query
    max host-side) — otherwise postings would be silently truncated.
    """
    rows = jnp.searchsorted(index.terms, q_terms)
    rows = jnp.clip(rows, 0, index.terms.shape[0] - 1)
    found = index.terms[rows] == q_terms
    start = index.term_block_start[rows]
    end = jnp.where(found, index.term_block_start[rows + 1], start)
    MB = index.max_blocks_per_term if max_blocks is None else max_blocks
    bidx = start[:, None] + jnp.arange(MB)[None, :]  # (Q, MB)
    in_term = bidx < end[:, None]
    bidx = jnp.where(in_term, bidx, 0)
    return rows, found, bidx, in_term


def _decode_score_blocks(index: BlockMaxIndex, flat, idf_flat, act_flat):
    """Decode + score a flat (S,) list of block ids under either storage
    layout — the one seam both the dense grid and the compacted survivor
    scorer go through. Fixed-stride indexes gather the pre-expanded
    (S, 32, 4) buffers; compact indexes hand the compressed rows plus
    per-block offsets to the fused decompress-and-score op, which
    expands exactly the selected blocks inside the computation (Pallas
    grid on TPU, per-survivor jnp gather on CPU). Identical (docids,
    tf, num) either way — asserted in tests."""
    if index.compact:
        return bm25_blocks_compact(
            index.cwords_docs, index.coff_docs[flat], index.bw_docs[flat],
            index.first_doc[flat], index.cwords_tf, index.coff_tf[flat],
            index.bw_tf[flat], idf_flat, act_flat, k1=index.k1)
    return bm25_blocks(
        index.packed_docs[flat], index.bw_docs[flat], index.first_doc[flat],
        index.packed_tf[flat], index.bw_tf[flat], idf_flat, act_flat,
        k1=index.k1)


def _score_blocks(index: BlockMaxIndex, bidx, active, idf_per_block,
                  doc_norm=None):
    """Exact BM25 partial scores for the selected blocks -> (D,) scores.

    ``bidx``/``active``/``idf_per_block`` may be the dense (Q, MB)
    candidate grid or a compacted (S,) survivor array — the scatter is
    over the flattened block list either way, and compaction preserves
    the flattened order, so the per-doc float accumulation order (and
    hence the scores, bit for bit) is identical on both paths."""
    if doc_norm is None:
        doc_norm = index.doc_norm
    flat = bidx.reshape(-1)
    docids, tf, num = _decode_score_blocks(
        index, flat, idf_per_block.reshape(-1),
        active.reshape(-1).astype(jnp.int32))
    denom = tf + doc_norm[docids]
    s = jnp.where(tf > 0, num / jnp.maximum(denom, 1e-9), 0.0)
    # docids are in-bounds by construction (local ids; inactive blocks -> 0)
    return jnp.zeros((index.n_docs,), jnp.float32).at[docids.reshape(-1)].add(
        s.reshape(-1), mode="promise_in_bounds")


def block_upper_bounds(index: BlockMaxIndex, bidx, in_term, idf_q,
                       avgdl=None):
    """Safe per-block score upper bound from the block's competitive
    impact pair: tf is monotone (-> block max tf) and the norm is
    monotone in doc length (-> the block's SHORTEST doc under ``avgdl``).
    For every doc d in the block: tf_d <= max_tf and dl_d >= min_dl, so
    score(d) <= idf*(k1+1)*max_tf / (max_tf + k1*(1-b+b*min_dl/avgdl)).
    Deleted docs may inflate max_tf / deflate min_dl — the bound only
    gets looser, never unsafe.

    SAFETY: the ``min_dl`` tightening is only valid when ``avgdl`` is the
    SAME mean length the evaluation's ``doc_norm`` was built from — a
    mismatched pair can under-bound real scores. Callers must therefore
    pass ``avgdl`` explicitly (the searcher passes its collection-global
    value; single-index paths pass ``index.avgdl`` alongside the baked
    ``index.doc_norm``); with ``avgdl=None`` the bound falls back to the
    stats-independent dl=0 floor, which is safe under ANY doc_norm."""
    mt = index.max_tf[bidx]
    min_norm = index.k1 * (1.0 - index.b)
    if index.min_dl is not None and avgdl is not None:
        min_norm = min_norm + index.k1 * index.b * index.min_dl[bidx] / avgdl
    ub = idf_q[:, None] * (index.k1 + 1.0) * mt / (mt + min_norm)
    return jnp.where(in_term & (mt > 0), ub, 0.0)


def _mask_live(scores, live):
    """Tombstone mask: deleted docs sink to -1, below every real BM25
    score (>= 0), so ``top_k`` never surfaces them while live zero-score
    docs still rank above. ``live`` is a (D,) bool vector (True = live);
    None means the segment carries no deletes and the scores pass through
    untouched (identical compiled graph to the pre-tombstone path)."""
    if live is None:
        return scores
    return jnp.where(live, scores, -1.0)


def _resolve_idf(index: BlockMaxIndex, q_terms, idf_q):
    """Default/validate the per-query-term idf vector (jit-compatible:
    the None branch is static)."""
    rows, found, _, _ = _gather_term_blocks(index, q_terms, 1)
    if idf_q is None:
        idf_q = index.idf[rows]
    return jnp.where(found, idf_q, 0.0)


# --------------------------------------------------------------------------
# dense evaluation (parity oracle + exhaustive path)
# --------------------------------------------------------------------------

def bm25_topk_dense(index: BlockMaxIndex, q_terms: jnp.ndarray, k: int = 10,
                    prune: bool = True, idf_q=None, doc_norm=None,
                    max_blocks=None, live=None, avgdl=None):
    """Fully-jittable dense evaluation — every candidate lane is computed.

    With ``prune=True`` this runs the original two-phase MaxScore test but
    only *masks* eliminated blocks (the pruning parity oracle: its top-k
    must equal the compacted path's bit for bit). With ``prune=False`` it
    is the exhaustive path. Either way serving cost is O(candidate
    blocks); the production pruned path is ``bm25_topk``.

    ``idf_q`` (Q,) and ``doc_norm`` (D,) default to the segment-local
    statistics baked into the index; a multi-segment searcher passes
    collection-global values instead. Pruning stays safe under overridden
    stats: the upper bounds only tighten with the block impact metadata
    when ``avgdl`` — the mean length ``doc_norm`` was built from — is
    supplied; with doc_norm overridden and no matching avgdl they fall
    back to the stats-independent dl=0 floor (see ``block_upper_bounds``).
    ``max_blocks`` narrows the per-term candidate window (see
    ``_gather_term_blocks``) — exact iff it covers every query term.
    ``live`` (D,) masks tombstoned docs out of BOTH phases: the phase-1
    threshold theta comes from masked scores (a lower theta only weakens
    pruning, never correctness), and the final top-k sees deleted docs at
    -1 — callers keep k <= live-doc count, so results are exactly the
    live index's (asserted equal to searching the compacted merge).
    """
    q_terms = q_terms.astype(jnp.int32)
    rows, found, bidx, in_term = _gather_term_blocks(index, q_terms,
                                                     max_blocks)
    if idf_q is None:
        idf_q = index.idf[rows]
    idf_q = jnp.where(found, idf_q, 0.0)
    idf_pb = jnp.broadcast_to(idf_q[:, None], bidx.shape)

    if not prune:
        scores = _mask_live(
            _score_blocks(index, bidx, in_term, idf_pb, doc_norm), live)
        vals, ids = jax.lax.top_k(scores, k)
        return vals, ids, {"blocks_scored": in_term.sum(),
                           "blocks_total": in_term.sum()}

    if avgdl is None and doc_norm is None:
        avgdl = index.avgdl  # baked stats: the self-consistent pair
    ub = block_upper_bounds(index, bidx, in_term, idf_q, avgdl)  # (Q, MB)
    # phase 1: score the top-UB half of candidate blocks
    n_cand = ub.size
    n_phase1 = max(n_cand // 2, min(n_cand, 8))
    thresh_ub = jnp.sort(ub.reshape(-1))[-n_phase1]
    phase1 = in_term & (ub >= thresh_ub)
    scores1 = _mask_live(
        _score_blocks(index, bidx, phase1, idf_pb, doc_norm), live)
    theta = jax.lax.top_k(scores1, k)[0][-1]  # valid lower bound on final theta

    # phase 2 (MaxScore test): block survives iff its UB plus every other
    # term's best-block UB can still beat theta.
    term_best = ub.max(axis=1)  # (Q,)
    others = term_best.sum() - term_best  # (Q,)
    needed = ub + others[:, None] > theta
    active = in_term & (phase1 | needed)
    scores = _mask_live(
        _score_blocks(index, bidx, active, idf_pb, doc_norm), live)
    vals, ids = jax.lax.top_k(scores, k)
    return vals, ids, {"blocks_scored": active.sum(),
                       "blocks_total": in_term.sum(), "theta": theta}


def bm25_exhaustive(index: BlockMaxIndex, q_terms, k: int = 10,
                    idf_q=None, doc_norm=None, live=None):
    return bm25_topk_dense(index, q_terms, k, prune=False,
                           idf_q=idf_q, doc_norm=doc_norm, live=live)


# --------------------------------------------------------------------------
# compacted pruned evaluation (the production path)
# --------------------------------------------------------------------------

def prune_candidates(index: BlockMaxIndex, q_terms, idf_q=None,
                     max_blocks=None, avgdl=None):
    """Jittable METADATA pass: per-candidate-block upper bounds, without
    touching (let alone decoding) any postings bytes. ``avgdl`` (traced
    scalar) supplies the mean doc length matching the evaluation's
    doc_norm — required for the tight impact bounds; None falls back to
    the safe dl=0 floor (see ``block_upper_bounds``). Returns
    ``(ub, in_term, bidx, idf_pb, bfirst, blast)``, each shaped (Q, MB) —
    the inputs of the device bound test (``prune_decide``) and survivor
    compaction. ``bfirst``/``blast`` are the candidate blocks' doc-id
    extents (garbage on pad entries — the test masks by ``in_term``); an
    index without ``last_doc`` reports the safe full-range extent
    [first, n_docs-1] instead, degrading the overlap bound toward the
    term-level one without ever under-bounding."""
    q_terms = q_terms.astype(jnp.int32)
    rows, found, bidx, in_term = _gather_term_blocks(index, q_terms,
                                                     max_blocks)
    if idf_q is None:
        idf_q = index.idf[rows]
    idf_q = jnp.where(found, idf_q, 0.0)
    ub = block_upper_bounds(index, bidx, in_term, idf_q, avgdl)
    idf_pb = jnp.broadcast_to(idf_q[:, None], bidx.shape)
    bfirst = index.first_doc[bidx].astype(jnp.int32)
    blast = (jnp.full(bidx.shape, index.n_docs - 1, jnp.int32)
             if index.last_doc is None
             else index.last_doc[bidx].astype(jnp.int32))
    return ub, in_term, bidx, idf_pb, bfirst, blast


def score_survivors(index: BlockMaxIndex, cb_ids, cb_idf, cb_act, cb_row,
                    n_rows: int, k: int, doc_norm=None, live=None):
    """Jittable compacted scorer over a batch-FLAT survivor list: entry j
    is block ``cb_ids[j]`` evaluated on behalf of query row ``cb_row[j]``
    (inactive padding entries contribute nothing). Decode + score exactly
    those blocks, scatter into the (n_rows, D) score matrix via
    row-offset indices, mask tombstones, per-row top-k.

    Flattening across the batch (instead of one bucket-padded array per
    query) means the padded size tracks the batch's TOTAL survivor count
    — a batch mixing heavy and light queries pays for what it prunes,
    not for its widest row. FLOPs are proportional to the bucket size,
    never the candidate count."""
    if doc_norm is None:
        doc_norm = index.doc_norm
    docids, tf, num = _decode_score_blocks(index, cb_ids, cb_idf,
                                           cb_act.astype(jnp.int32))
    denom = tf + doc_norm[docids]
    s = jnp.where(tf > 0, num / jnp.maximum(denom, 1e-9), 0.0)
    # row-major survivor order keeps each row's scatter contributions in
    # candidate order — per-doc float accumulation matches the dense path
    fidx = cb_row.astype(jnp.int32)[:, None] * index.n_docs + docids
    scores = jnp.zeros((n_rows * index.n_docs,), jnp.float32
                       ).at[fidx.reshape(-1)].add(s.reshape(-1),
                                                  mode="promise_in_bounds")
    scores = scores.reshape(n_rows, index.n_docs)
    if live is not None:
        scores = jnp.where(live[None, :], scores, -1.0)
    return jax.lax.top_k(scores, k)


def score_survivors_midgrid(index: BlockMaxIndex, cb_ids, cb_idf, cb_act,
                            cb_row, cb_ubf, theta_rows, n_rows: int, k: int,
                            doc_norm=None):
    """``score_survivors`` with in-grid theta tightening (the midgrid
    variant of the Pallas skip kernel): after each sequential grid step
    the kernel folds the step's per-lane pessimistic partials
    ``num / (tf + max(doc_norm))`` into a per-row running k-th-best lower
    bound (seeded from ``theta_rows``), and later steps ZERO any block
    whose stored full-score UB ``cb_ubf`` falls strictly below it.

    Soundness: each lane of a block is a distinct doc whose true score is
    at least its pessimistic partial, so a block's k-th largest lane
    partial is witnessed by k distinct docs — a valid lower bound on the
    row's final k-th score, as is ``theta_rows`` (the caller's securing
    contract). A zeroed block therefore only held docs that can neither
    make the top-k nor tie it (strict <), and zeroing adds +0.0 into
    non-negative partial sums, so surfaced top-k values stay bit-
    identical. VALID ONLY with no tombstones (a deleted doc is not a
    legitimate witness) — the caller gates on ``live is None`` — and for
    the fixed-stride (non-compact) layout.

    Returns ``(vals, ids, n_skipped)``."""
    if doc_norm is None:
        doc_norm = index.doc_norm
    theta_l = jnp.zeros((1, BLOCK), jnp.float32).at[0, :n_rows].set(
        jnp.asarray(theta_rows, jnp.float32))
    docids, tf, num, skip = bm25_blocks_midgrid(
        index.packed_docs[cb_ids], index.bw_docs[cb_ids],
        index.first_doc[cb_ids], index.packed_tf[cb_ids],
        index.bw_tf[cb_ids], cb_idf, cb_act.astype(jnp.int32),
        cb_row.astype(jnp.int32), jnp.asarray(cb_ubf, jnp.float32),
        theta_l, jnp.max(doc_norm), k=k, k1=index.k1,
        block_rows=MIDGRID_BLOCK_ROWS)
    denom = tf + doc_norm[docids]
    s = jnp.where(tf > 0, num / jnp.maximum(denom, 1e-9), 0.0)
    fidx = cb_row.astype(jnp.int32)[:, None] * index.n_docs + docids
    scores = jnp.zeros((n_rows * index.n_docs,), jnp.float32
                       ).at[fidx.reshape(-1)].add(s.reshape(-1),
                                                  mode="promise_in_bounds")
    return (*jax.lax.top_k(scores.reshape(n_rows, index.n_docs), k),
            skip.sum())


def _pow2ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def survivor_bucket(n_surv: int) -> int:
    """Bucket (compiled shape) for a survivor count: next power of two,
    floored at ``MIN_BUCKET`` — so the compacted scorer compiles at most
    log2(max candidates) distinct shapes per (k, segment)."""
    return max(MIN_BUCKET, _pow2ceil(max(n_surv, 1)))


# float32 slack of the device bound test. The device sums float32 upper
# bounds in float32, so a bound can round below its exact value by up to
# (Q-1) * 2^-24 relative, and the product by (1 + slack) rounds once more:
# a slack of 2^-20 covers queries of up to 15 terms (longer ones get
# more), so the device keeps every block the exact test keeps.
BOUND_SLACK = 2.0 ** -20


def bound_slack(n_terms: int) -> float:
    """Relative slack of the float32 bound test for ``n_terms`` terms."""
    return BOUND_SLACK * max(1, _pow2ceil(n_terms + 1) // 16)


def _best_overlapping(f3, l3, x):
    """For every block j of term t: the sum over the OTHER terms `to` of
    ``max x[to, i]`` over `to`'s blocks i whose doc-id range [f, l] meets
    j's (0 where none does). ``f3``/``l3``/``x`` are (B, Q, MB); ``x``
    must be 0 on pad blocks. Every (j, i) pair is compared at once and
    reduced in place — a compare-and-max fusion, no gather — one other
    term at a time, so nothing of size MB^2 is held."""
    B, Q, MB = x.shape
    fj, lj = f3[..., None], l3[..., None]                  # (B, t, j, 1)
    terms = jnp.arange(Q)[None, :, None]
    out = jnp.zeros(x.shape, x.dtype)
    for to in range(Q):
        meets = ((l3[:, to, None, None, :] >= fj)
                 & (f3[:, to, None, None, :] <= lj))       # (B, t, j, i)
        best = jnp.where(meets, x[:, to, None, None, :], 0).max(-1)
        out = out + jnp.where(terms != to, best, 0)
    return out


def bound_test(ub, in_term, bf, bl, theta, bmw: bool):
    """Phase 2 of ``pruned_eval`` on the device: the blocks that survive
    the bound test at ``theta`` (B,), float32 throughout.

    ``ub``/``in_term``/``bf``/``bl`` are the metadata pass's (B, Q, MB)
    arrays (``bf``/``bl``, the blocks' first and last doc ids, read only
    by ``bmw``). ``bmw`` runs the doc-range-overlap bound with
    non-essential term elimination, else the term-level MaxScore test.
    A block survives iff ``bound * (1 + slack) > theta``, and a term is
    non-essential only if its prefix sum times ``(1 + slack)`` is still
    ``<= theta`` (``bound_slack``): the survivors are a superset of the
    exact (float64) test's and the non-essential terms a subset, so the
    float32 rounding can cost work but never a result.

    Returns ``(surv (B, Q, MB) bool, bound (B, Q, MB) float32 — without
    the slack, non-essential (B, Q) bool, blocks kept only by the slack
    (scalar))``."""
    B, Q, MB = ub.shape
    grow = jnp.float32(1.0 + bound_slack(Q))
    ub = jnp.where(in_term, ub, 0.0)
    term_best = ub.max(axis=2)                             # (B, Q)
    if bmw:
        # doc-range-overlap "others" bound: a doc of block j that also
        # carries term `to` sits in one of `to`'s blocks, whose range
        # therefore meets j's
        bound = ub + _best_overlapping(bf, bl, ub)
    else:
        # term-level MaxScore: every other term helps with its global
        # best block, wherever that block lives in doc space
        others = jnp.where(~jnp.eye(Q, dtype=bool)[None],
                           term_best[:, :, None], 0.0).sum(1)
        bound = ub + others[:, :, None]
    theta3 = theta[:, None, None]
    surv = in_term & (bound * grow > theta3)
    n_margin = (surv & (theta3 >= bound)).sum()
    ness = jnp.zeros((B, Q), bool)
    if bmw:
        # non-essential list elimination: sort terms by ascending best
        # contribution; the maximal prefix whose cumulative sum cannot
        # beat theta is non-essential. A winner (true score > theta) must
        # carry >= 1 essential term, so non-essential terms generate no
        # candidates of their own — their blocks are kept only when they
        # range-overlap a SURVIVING essential block (those are the only
        # places a winner's remaining contributions can live).
        order = jnp.argsort(term_best, axis=1, stable=True)
        csum = jnp.cumsum(jnp.take_along_axis(term_best, order, 1), 1)
        ness = jnp.take_along_axis(csum * grow <= theta[:, None],
                                   jnp.argsort(order, axis=1), 1)
        ess_surv = (surv & ~ness[:, :, None]).astype(jnp.int32)
        touches = _best_overlapping(bf, bl, ess_surv) > 0
        surv = jnp.where(ness[:, :, None], surv & touches, surv)
    return surv, bound, ness, n_margin


@functools.partial(jax.jit, static_argnames=("n_phase1",))
def probe_pick(ub, in_term, bidx, idf_pb, n_phase1: int):
    """Phase 1's probe on the device: per query the ``n_phase1``
    highest-UB candidate blocks, compacted into one flat batch list
    (padded to a power of two) for the survivor scorer. Returns
    ``((ids, idf, act, row), pos (B, P1), act (B, P1))``; ``pos``/``act``
    mark the probe blocks that ``prune_decide`` keeps unconditionally."""
    B = ub.shape[0]
    ub, in_term = ub.reshape(B, -1), in_term.reshape(B, -1)
    _, pos = jax.lax.top_k(jnp.where(in_term, ub, -1.0), n_phase1)
    act = jnp.take_along_axis(in_term, pos, 1)
    pad = _pow2ceil(B * n_phase1) - B * n_phase1

    def flat(x):
        return jnp.pad(jnp.take_along_axis(x.reshape(B, -1), pos, 1
                                           ).reshape(-1), (0, pad))
    row = jnp.repeat(jnp.arange(B, dtype=jnp.int32), n_phase1)
    return ((flat(bidx), flat(idf_pb), jnp.pad(act.reshape(-1), (0, pad)),
             jnp.pad(row, (0, pad))), pos, act)


@jax.jit
def probe_theta(vals, theta0):
    """theta (B,) = max(the probe's k-th best score, ``theta0``): a valid
    lower bound on each query's final k-th score."""
    return jnp.maximum(vals[:, -1], theta0)


@functools.partial(jax.jit, static_argnames=("bmw",))
def prune_decide(ub, in_term, bf, bl, theta, keep_pos, keep_act, bmw: bool):
    """The pruning decision of one segment, on the device: the bound test
    at ``theta`` (B,) (``bound_test``), and the phase-1 probe blocks
    (``keep_pos`` (B, P1) where ``keep_act``) kept unconditionally: the
    impact bound can be exactly achieved (the block's best doc IS its
    (max_tf, min_dl) pair), so a probed doc at exactly theta must stay
    scored. Without a probe the caller passes zeros.

    Returns ``(rank (B, N), ubf (B, N), counts)``: ``rank`` counts the
    survivors up to and including each candidate of the row-major
    flattened grid (what ``compact_survivors`` reads); ``ubf`` is each
    block's slack-inflated bound, +inf on the probe blocks so the midgrid
    kernel's in-grid skip can never drop them; ``counts`` (int32 (4,))
    holds the survivors, the candidate blocks, the non-essential terms
    that have blocks and ``blocks_margin_kept`` — the one fetch the host
    needs."""
    B, Q, MB = ub.shape
    surv, bound, ness, n_margin = bound_test(ub, in_term, bf, bl, theta,
                                             bmw)
    rows = jnp.arange(B)[:, None]
    surv = surv.reshape(B, -1)
    surv = surv.at[rows, keep_pos].set(surv[rows, keep_pos] | keep_act)
    ubf = (bound * jnp.float32(1.0 + bound_slack(Q))).reshape(B, -1)
    ubf = ubf.at[rows, keep_pos].set(
        jnp.where(keep_act, jnp.inf, ubf[rows, keep_pos]))
    # inclusive running count in row-major order: per row, then the rows
    # before it (one long cumsum compiles far slower on the TPU)
    rank = jnp.cumsum(surv.astype(jnp.int32), 1)
    rank = rank + (jnp.cumsum(rank[:, -1]) - rank[:, -1])[:, None]
    counts = jnp.stack([rank[-1, -1], in_term.sum(),
                        (ness & in_term.any(2)).sum(), n_margin]
                       ).astype(jnp.int32)
    return rank, ubf, counts


@functools.partial(jax.jit, static_argnames=("bucket",))
def compact_survivors(rank, bidx, idf_pb, ubf, bucket: int):
    """Survivor compaction on the device: gather the flattened positions
    of surviving candidate blocks — across the WHOLE batch — into one
    dense, bucket-padded flat list with per-entry query-row attribution.

    ``rank``/``ubf`` are (B, N) over the flattened candidate grid
    (``prune_decide``), ``bidx``/``idf_pb`` the metadata's (B, Q, MB).
    Entry j is the candidate where the row-major running count first
    reaches j + 1, so entries are sorted by (row, grid position), which
    keeps each row's compacted scatter contributions in the dense path's
    order (bit-identity); ``bucket`` (``survivor_bucket`` of the fetched
    count) must hold every survivor. Returns ``(cb_ids, cb_idf, cb_act,
    cb_row, cb_ubf)``, each (bucket,); padding entries are inactive, with
    UB +inf (never midgrid-skipped)."""
    N = rank.shape[1]
    rank = rank.reshape(-1)
    j = jnp.arange(bucket, dtype=jnp.int32)
    act = j < rank[-1]
    pos = jnp.where(act, jnp.searchsorted(rank, j + 1, method="scan"), 0)
    return (jnp.where(act, bidx.reshape(-1)[pos], 0),
            jnp.where(act, idf_pb.reshape(-1)[pos], 0.0), act,
            jnp.where(act, pos // N, 0).astype(jnp.int32),
            jnp.where(act, ubf.reshape(-1)[pos], jnp.inf))


def _theta_f32(theta0, B: int) -> np.ndarray:
    """(B,) float64 host bound -> float32, rounded down: a lower theta
    only keeps more blocks."""
    t = np.broadcast_to(np.asarray(theta0, np.float64), (B,))
    t32 = t.astype(np.float32)
    return np.where(t32 > t, np.nextafter(t32, np.float32(-np.inf)), t32)


def pruned_eval(meta, scorer_for, q2d, idf2d, theta0=None,
                n_phase1: int = PHASE1_BLOCKS, bmw: bool = True,
                scorer_mid_for=None):
    """Pruned evaluation of one segment over a (B, Q) query batch, the
    decision on the device.

    ``meta(q2d, idf2d)``       -> (ub, in_term, bidx, idf_pb, bfirst,
                                  blast), (B, Q, MB) device arrays
                                  (``prune_candidates``, possibly
                                  jitted/vmapped by the caller).
    ``scorer_for(n_blocks)``   -> fn(cb_ids, cb_idf, cb_act, cb_row)
                                  evaluating a flat (n_blocks,) compacted
                                  survivor list (``score_survivors``) to
                                  (vals (B, k), ids (B, k)). The caller
                                  owns jit caching per bucket shape.
    ``scorer_mid_for``         optional midgrid variant for the SURVIVOR
                                  stage: fn(cb_ids, cb_idf, cb_act,
                                  cb_row, cb_ubf, theta_rows) -> (vals,
                                  ids, n_skipped) — the kernel folds a
                                  running k-th-best lower bound across
                                  grid steps and zeroes later blocks
                                  whose stored full-score UB ``cb_ubf``
                                  falls below it (see
                                  ``score_survivors_midgrid``). The
                                  phase-1 probe always uses the plain
                                  scorer (theta is not known yet).
    ``theta0``                 (B,) or scalar: an externally-known lower
                                  bound on each query's final k-th score
                                  (the searcher passes the running global
                                  bound — cross-segment theta sharing).
    ``bmw``                    True (default) runs the doc-range-overlap
                                  bound + non-essential list elimination;
                                  False keeps the term-level MaxScore
                                  test (the bench A/B baseline).

    Protocol, all on the device: metadata pass -> score the ``n_phase1``
    highest-UB blocks per query for theta (``probe_pick``; skipped
    entirely when every query already holds a positive external bound) ->
    block-max WAND test at max(theta_phase1, theta0) (``prune_decide``)
    -> compact the survivors (``compact_survivors``, a power-of-two bucket
    over the batch TOTAL) -> compacted exact scoring. The host fetches
    the decision's counts, which pick the bucket, and the final top-k.

    Exactness under BMW: for a doc d with true score > theta, every block
    of d survives — the block's own UB majorizes d's contribution from
    that term, and for every OTHER query term d carries, d's block there
    shares d and therefore range-overlaps, so its UB enters the overlap
    sum: bound >= true(d) > theta. Non-essential elimination preserves
    this: a doc scoring above theta must have at least one essential term
    (the non-essential prefix's term-best sum is <= theta by
    construction), its essential blocks survive the bound test, and its
    non-essential blocks range-overlap one of them — the condition under
    which non-essential blocks are kept. Docs at or below theta may end
    up partially scored, but their computed score never exceeds their
    true score, so any value the final top-k surfaces is exact (ties at
    theta are covered by the unconditionally-kept phase-1 probes / the
    ``theta0`` securing contract). The float32 slack (``bound_test``)
    only adds survivors.
    Returns ``(vals, ids, PruneStats)``, ``vals``/``ids`` fetched to the
    host.
    """
    B, Q = q2d.shape
    with span("prune.meta"):
        ub, in_term, bidx, idf_pb, bf, bl = jax.block_until_ready(
            meta(q2d, idf2d))
    t0 = _theta_f32(0.0 if theta0 is None else theta0, B)
    P1 = min(n_phase1, ub.shape[1] * ub.shape[2])

    # phase 1: probe the highest-UB blocks for a threshold. The probe set
    # is compacted too (fixed shape P1), so phase-1 cost is O(P1), not
    # O(candidates)/2 like the dense oracle's. A caller that already
    # holds a positive bound for every query (the searcher's shared theta
    # after the first segment) skips the probe entirely — later segments
    # pay ONLY for their survivors.
    probed = 0
    if not bool(np.all(t0 > 0)):
        with span("prune.probe"):
            p1, keep_pos, keep_act = probe_pick(ub, in_term, bidx, idf_pb,
                                                n_phase1=P1)
            probed = p1[0].shape[0]
            vals1, _ = scorer_for(probed)(*p1)
            theta = jax.block_until_ready(probe_theta(vals1, t0))
    else:
        theta = t0
        keep_pos = np.zeros((B, P1), np.int32)
        keep_act = np.zeros((B, P1), bool)

    with span("prune.bound"):
        rank, ubf, counts = prune_decide(
            ub, in_term, bf, bl, theta, keep_pos, keep_act, bmw=bmw)
        n_surv, n_cand, n_elim, n_margin = jax.device_get(counts).tolist()
    with span("prune.compact"):
        cb_ids, cb_idf, cb_act, cb_row, cb_ubf = compact_survivors(
            rank, bidx, idf_pb, ubf, bucket=survivor_bucket(n_surv))
    n_skipped = 0
    with span("score.survivors"):
        if scorer_mid_for is not None:
            out = scorer_mid_for(cb_ids.shape[0])(
                cb_ids, cb_idf, cb_act, cb_row, cb_ubf, theta)
            vals, ids, n_skipped = jax.device_get(out)
        else:
            vals, ids = jax.device_get(scorer_for(cb_ids.shape[0])(
                cb_ids, cb_idf, cb_act, cb_row))
    # queries/batches stay zero here: this evaluates ONE segment of a
    # batch; the caller (searcher / bm25_topk) counts the batch once.
    stats = PruneStats(
        segments_visited=1,
        blocks_candidate=n_cand,
        blocks_survived=n_surv,
        blocks_scored=probed + cb_ids.shape[0],
        terms_eliminated=n_elim,
        blocks_skipped_midgrid=int(n_skipped),
        blocks_margin_kept=n_margin)
    return vals, ids, stats


def bm25_topk(index: BlockMaxIndex, q_terms: jnp.ndarray, k: int = 10,
              prune: bool = True, idf_q=None, doc_norm=None,
              max_blocks=None, live=None, theta0=None, avgdl=None,
              bmw: bool = True, midgrid: bool = True):
    """Top-k BM25: ``(scores (k,), doc_ids (k,), stats dict)``.

    ``prune=True`` runs the compacted pruned path (its decision runs on
    the device, but the host picks each survivor bucket from a fetched
    count, so this function itself is NOT jittable — the searcher caches
    jitted versions of its metadata pass and scorers); ``prune=False``
    falls back to the dense exhaustive evaluation. Results are identical
    either way. See ``pruned_eval`` for the protocol and the remaining
    keyword contracts on ``bm25_topk_dense``.

    ``theta0`` contract (cross-segment threshold sharing): the caller
    asserts that k results with score >= theta0 are already secured
    ELSEWHERE (previous segments). Results strictly above theta0 are
    exact; docs tied at exactly theta0 may be dropped — their slots are
    covered by the securing results, so a merge over segments is still
    value-exact vs the force-merged index.

    ``bmw`` selects the doc-range-overlap bound + non-essential list
    elimination (default) vs the term-level MaxScore baseline;
    ``midgrid`` additionally runs the survivor scorer through the
    in-grid theta-tightening kernel when its gates hold (no tombstones,
    fixed-stride layout, k small enough for the in-kernel fold).
    """
    if not prune:
        return bm25_topk_dense(index, q_terms, k, prune=False, idf_q=idf_q,
                               doc_norm=doc_norm, max_blocks=max_blocks,
                               live=live)
    q_terms = jnp.asarray(q_terms, jnp.int32)
    idf1 = _resolve_idf(index, q_terms, idf_q)
    if avgdl is None and doc_norm is None:
        avgdl = index.avgdl  # baked stats: the self-consistent pair

    def meta(q2d, idf2d):
        return jax.vmap(
            lambda q, f: prune_candidates(index, q, f, max_blocks,
                                          avgdl))(q2d, idf2d)

    def scorer_for(_n):
        return lambda ci, cf, ca, cr: score_survivors(
            index, ci, cf, ca, cr, 1, k, doc_norm, live)

    scorer_mid_for = None
    if midgrid and live is None and not index.compact \
            and k <= MIDGRID_MAX_K:
        def scorer_mid_for(_n):
            return lambda ci, cf, ca, cr, cu, th: score_survivors_midgrid(
                index, ci, cf, ca, cr, cu, th, 1, k, doc_norm)

    vals, ids, stats = pruned_eval(meta, scorer_for, q_terms[None],
                                   idf1[None], theta0=theta0, bmw=bmw,
                                   scorer_mid_for=scorer_mid_for)
    stats.queries, stats.batches = 1, 1
    return vals[0], ids[0], {
        "blocks_scored": stats.blocks_scored,
        "blocks_survived": stats.blocks_survived,
        "blocks_total": stats.blocks_candidate,
        "prune_stats": stats,
    }


# --------------------------------------------------------------------------
# host oracle of the device bound test (tests compare ``bound_test``
# against it; nothing on the serving path calls it)
# --------------------------------------------------------------------------

def _row_searchsorted(keys: np.ndarray, queries: np.ndarray,
                      side: str, stride: int) -> np.ndarray:
    """Row-wise ``searchsorted``: for each row r, positions of
    ``queries[r]`` within the sorted ``keys[r]``. One flat searchsorted
    over row-offset values (every entry lives in [0, stride)), instead of
    a Python loop over rows."""
    R, MB = keys.shape
    off = np.arange(R, dtype=np.int64) * stride
    flat = np.searchsorted((keys + off[:, None]).reshape(-1),
                           (queries + off[:, None]).reshape(-1), side)
    return flat.reshape(R, -1) - np.arange(R)[:, None] * MB


def _range_max(rows: np.ndarray, lo: np.ndarray, hi: np.ndarray
               ) -> np.ndarray:
    """Per-row range max: ``max(rows[r, lo[r,j]:hi[r,j]])`` (0.0 for an
    empty range), vectorized with a sparse table — O(R*MB*log MB) build,
    O(1) per query. The overlap-bound test runs one of these per ordered
    term pair, so the whole BMW pass stays O(B*Q^2*MB*log MB) host work
    on metadata only."""
    R, MB = rows.shape
    length = hi - lo
    res = np.zeros(lo.shape, rows.dtype)
    if MB == 0:
        return res
    tables = [rows]
    while (1 << len(tables)) <= MB:
        w = 1 << (len(tables) - 1)
        prev = tables[-1]
        tables.append(np.maximum(prev[:, :MB - 2 * w + 1],
                                 prev[:, w:MB - w + 1]))
    # floor(log2(length)) per query, exact for the int sizes here
    lvl = np.frexp(np.maximum(length, 1))[1] - 1
    for lv in range(len(tables)):
        sel = (lvl == lv) & (length > 0)
        if not sel.any():
            continue
        ri, qi = np.nonzero(sel)
        w = 1 << lv
        res[sel] = np.maximum(tables[lv][ri, lo[ri, qi]],
                              tables[lv][ri, hi[ri, qi] - w])
    return res


def _bmw_overlap_others(ub3, f3, l3, sentinel: int):
    """Doc-range-overlap "others" bound (true block-max WAND): for every
    candidate block j of term t, the sum over OTHER query terms t' of the
    max upper bound among t''s blocks whose doc-id range [first, last]
    intersects block j's. Exact majorization: a doc d in block j that
    also carries term t' sits in exactly one of t''s blocks, and that
    block shares d with j — so its range overlaps j's and its UB enters
    the sum. Strictly tighter than the term-level ``sum - term_best``
    bound whenever any other term's best block lies outside j's range
    (balanced disjunctions on iid corpora — the workload term-level
    MaxScore cannot prune).

    ``ub3``/``f3``/``l3`` are (B, Q, MB) host arrays; pad entries must
    already hold ``sentinel`` in f3/l3 (sorted-row invariant; sentinel
    ranges only ever "overlap" other sentinel ranges, whose UB is 0).
    Returns (B, Q, MB) overlap-others, garbage on pad entries."""
    B, Q, MB = ub3.shape
    stride = sentinel + 2
    overlap = np.zeros((B, Q, MB))
    for to in range(Q):
        # one sparse table + two flat searchsorteds per "other" term,
        # shared across every t != to
        keys_l = l3[:, to, :]
        keys_f = f3[:, to, :]
        for t in range(Q):
            if t == to:
                continue
            # blocks of `to` overlapping [f, l]: first with last >= f
            # through last with first <= l
            lo = _row_searchsorted(keys_l, f3[:, t, :], "left", stride)
            hi = _row_searchsorted(keys_f, l3[:, t, :], "right", stride)
            overlap[:, t, :] += _range_max(ub3[:, to, :], lo, hi)
    return overlap


def _bound_test(ub, in_term, bf, bl, theta, Q: int, bmw: bool):
    """The exact (float64) bound test that ``bound_test`` runs on the
    device in float32: the blocks that survive at ``theta``.
    ``ub``/``in_term`` are (B, Q*MB); ``bf``/``bl`` the blocks' first and
    last doc ids (read only by ``bmw``). Returns ``(surv (B, Q*MB) bool,
    bound (B, Q*MB), non-essential terms (B, Q) bool)``."""
    B = ub.shape[0]
    ub3 = ub.reshape(B, Q, -1)
    MB = ub3.shape[2]
    term_best = ub3.max(axis=2)                            # (B, Q)
    ness = np.zeros((B, Q), bool)
    if bmw:
        # doc-range-overlap "others" bound. Pad entries get a sentinel
        # extent past every real doc id: rows stay sorted (in_term is a
        # prefix mask, pads trail) and sentinel ranges only overlap other
        # sentinel ranges, whose UB is 0.
        in3 = in_term.reshape(B, Q, MB)
        sentinel = int(max(bl.max(initial=0), bf.max(initial=0)) + 1)
        f3 = np.where(in3, bf.reshape(B, Q, MB), sentinel)
        l3 = np.where(in3, bl.reshape(B, Q, MB), sentinel)
        bound3 = ub3 + _bmw_overlap_others(ub3, f3, l3, sentinel)
        base = in3 & (bound3 > theta[:, None, None])
        # non-essential list elimination: sort terms by ascending best
        # contribution; the maximal prefix whose cumulative sum cannot
        # beat theta is non-essential. A winner (true score > theta) must
        # carry >= 1 essential term, so non-essential terms generate no
        # candidates of their own — their blocks are kept only when they
        # range-overlap a SURVIVING essential block (those are the only
        # places a winner's remaining contributions can live).
        order = np.argsort(term_best, axis=1, kind="stable")
        csum = np.cumsum(np.take_along_axis(term_best, order, 1), axis=1)
        np.put_along_axis(ness, order, csum <= theta[:, None], 1)
        if ness.any():
            ess_surv = (base & ~ness[:, :, None]).astype(np.float64)
            touches = _bmw_overlap_others(ess_surv, f3, l3, sentinel) > 0
            base = np.where(ness[:, :, None], base & touches, base)
        return base.reshape(B, -1), bound3.reshape(B, -1), ness
    # term-level MaxScore baseline: every other term helps with its
    # global best block, wherever that block lives in doc space
    others = term_best.sum(axis=1, keepdims=True) - term_best
    bound = (ub3 + others[:, :, None]).reshape(B, -1)
    return in_term & (bound > theta[:, None]), bound, ness
