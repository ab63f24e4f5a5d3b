"""Segment-native read path: per-segment readers, multi-segment search.

The write side (``core/indexer.py`` -> ``core/merge.py``) produces a *set*
of immutable segments whose doc-id spaces are disjoint by construction
(each flush covers a fresh doc range; merges union their inputs). The read
side built here makes that set searchable **while it is still being
built** — the near-real-time shape of production engines (write-read
decoupling), rather than the paper's force-merged end state:

  ``build_block_index``   vectorized (numpy CSR block-alignment) builder of
                          the device-resident ``BlockMaxIndex`` for one
                          segment; bit-identical to the scalar reference
                          ``build_block_index_loop`` it replaced.
  ``SegmentReader``       one open segment: its block-max index, the
                          local->absolute doc-id map, the live-doc mask
                          (tombstones), and a cache of jitted evaluators —
                          the dense exhaustive one, plus the two device
                          stages of the compacted pruned path (metadata
                          pass + survivor scorer, see ``core/query.py``).
  ``IndexSearcher``       an immutable snapshot over a list of readers.
                          Evaluates each segment under collection-GLOBAL
                          statistics computed from LIVE docs only (summed
                          live df -> idf, live avgdl -> doc_norm), masks
                          tombstones inside the evaluation, and merges
                          per-segment top-k — so results equal searching
                          the force-merged COMPACTED index exactly, and a
                          deleted doc is never returned. With ``prune=True``
                          (the default) segments are visited in descending
                          best-possible-score order and each later segment
                          starts from the running global k-th-score lower
                          bound (cross-segment theta sharing: later
                          segments prune harder, some are skipped outright)
                          — exactness is preserved because theta is always
                          a valid lower bound on the final k-th score.
  ``ReaderCache``         keyed by ``Segment.seg_id``: successive refreshes
                          only build readers for segments they have not
                          seen, so a merge cascade costs one reader build
                          for the merged output, not one per input. A
                          delete only swaps the bitmap (``with_deletes``
                          keeps ``base_id``), so the cache REOPENS the
                          existing reader over the new liveness — the
                          packed index and its compiled evaluators are
                          reused, not rebuilt.

Refresh lifecycle (see ``DistributedIndexer.refresh``): the indexer flushes
its in-memory buffer, snapshots ``MergeDriver.live_segments()``, and asks
the ``ReaderCache`` for a searcher over that snapshot. The returned
``IndexSearcher`` stays valid forever — later flushes and merges create new
Segment objects and never mutate old ones — so serving threads can keep an
old searcher while indexing proceeds, and swap in a fresh one per refresh.
"""
from __future__ import annotations

import collections
import itertools
import threading
from dataclasses import dataclass, field, fields, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.query import (BLOCK, MIDGRID_MAX_K, BlockMaxIndex,
                              PruneStats, bm25_topk_dense, prune_candidates,
                              pruned_eval, score_survivors,
                              score_survivors_midgrid)
from repro.core.segments import Segment, live_posting_stats
from repro.kernels.postings_pack import ops as pack_ops
from repro.kernels.postings_pack import ref as pack_ref
from repro.spans import span


# --------------------------------------------------------------------------
# shape-keyed compiled-evaluator sharing
# --------------------------------------------------------------------------
# jit closures used to bake each reader's index arrays into their traces,
# so every NRT flush compiled fresh evaluators for its new segment even
# when the shapes matched a segment already open. The process-global cache
# below keys compiled fns on (evaluator kind + static config + array
# shape/dtype signature) and passes the index arrays AS ARGUMENTS:
# same-shaped SegmentReaders share one compiled evaluator, steady-state
# churn is near-compile-free, and ``warm_searcher`` collapses to cache
# probes. ``evaluator_cache_hits`` counts reader-level lookups that found
# their evaluator precompiled (surfaced via ``envelope_report``).

_IDX_FIELDS_DENSE = ("terms", "term_block_start", "idf", "packed_docs",
                     "bw_docs", "packed_tf", "bw_tf", "first_doc", "max_tf",
                     "doc_norm", "min_dl", "last_doc")
_IDX_FIELDS_COMPACT = ("terms", "term_block_start", "idf", "bw_docs",
                       "bw_tf", "first_doc", "max_tf", "doc_norm", "min_dl",
                       "last_doc", "cwords_docs", "coff_docs",
                       "cwords_tf", "coff_tf")

# LRU-bounded: a steady-state serving fleet cycles through a handful of
# shapes, but a long-lived process that churns through MANY distinct
# segment shapes (the test suite, a backfill) would otherwise pin every
# compiled executable it ever built — XLA:CPU's JIT degrades (and can
# crash) when thousands of executables stay live, so evict cold shapes
# and let their device code be reclaimed.
_EVAL_CACHE_CAP = 128
_EVAL_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_EVAL_HITS = [0]
_EVAL_LOCK = threading.Lock()


def _index_arrays(index: BlockMaxIndex) -> tuple:
    """The index's device arrays in canonical argument order (layout-
    dependent: the compact layout ships plane rows instead of the
    fixed-stride packed buffers)."""
    names = _IDX_FIELDS_COMPACT if index.compact else _IDX_FIELDS_DENSE
    return tuple(getattr(index, n) for n in names)


def _index_statics(index: BlockMaxIndex) -> tuple:
    return (index.compact, index.n_docs, index.max_blocks_per_term,
            index.k1, index.b)


def _rebuild_index(arrs: tuple, compact: bool, n_docs: int, mbpt: int,
                   k1: float, b: float) -> BlockMaxIndex:
    """Reassemble a ``BlockMaxIndex`` view over traced array arguments
    inside a shared evaluator's trace. ``avgdl`` stays at its dummy
    default on purpose: every searcher-path caller passes explicit
    collection stats (doc_norm/avgdl arguments), so the baked value is
    never read."""
    names = _IDX_FIELDS_COMPACT if compact else _IDX_FIELDS_DENSE
    kw = dict(zip(names, arrs))
    if compact:
        kw.setdefault("packed_docs", None)
        kw.setdefault("packed_tf", None)
    return BlockMaxIndex(n_docs=n_docs, max_blocks_per_term=mbpt,
                         k1=k1, b=b, **kw)


def _shared_evaluator(kind_key: tuple, index: BlockMaxIndex, build):
    """Fetch or compile the shared evaluator for this kind + the index's
    shape signature. ``build(statics)`` must return a jitted fn whose
    leading argument is the ``_index_arrays`` tuple. Returns
    ``(fn, was_cached)``; duplicate concurrent builds are benign (one
    copy wins the insert)."""
    statics = _index_statics(index)
    shapes = tuple((tuple(a.shape), str(a.dtype))
                   for a in _index_arrays(index))
    key = (kind_key, statics, shapes)
    with _EVAL_LOCK:
        fn = _EVAL_CACHE.get(key)
        if fn is not None:
            _EVAL_CACHE.move_to_end(key)
            return fn, True
    fn = build(statics)
    with _EVAL_LOCK:
        fn = _EVAL_CACHE.setdefault(key, fn)
        _EVAL_CACHE.move_to_end(key)
        while len(_EVAL_CACHE) > _EVAL_CACHE_CAP:
            _EVAL_CACHE.popitem(last=False)
    return fn, False


def evaluator_cache_hits() -> int:
    """Reader-level evaluator lookups served by the shared cache (how
    often NRT churn avoided a compile)."""
    with _EVAL_LOCK:
        return _EVAL_HITS[0]


def _count_eval_hit(cached: bool) -> None:
    if cached:
        with _EVAL_LOCK:
            _EVAL_HITS[0] += 1


# --------------------------------------------------------------------------
# per-segment index construction
# --------------------------------------------------------------------------

def _finish_index(seg: Segment, deltas: np.ndarray, tfs: np.ndarray,
                  first_doc: np.ndarray, max_tf: np.ndarray,
                  term_nb: np.ndarray, df: np.ndarray,
                  k1: float, b: float, min_dl: np.ndarray,
                  dl: np.ndarray = None,
                  compact: bool = False,
                  last_doc: np.ndarray = None) -> BlockMaxIndex:
    """Shared tail of both builders: pack blocks + assemble the index.

    ``dl`` is the LOCAL-SLOT-ordered doc-length vector (defaults to the
    segment's natural order; a reordered build passes the permuted one so
    slot d's norm describes the doc that actually lives in slot d).
    ``compact=True`` keeps only the live bit-plane rows + per-block row
    offsets (the fused decompress-and-score layout) instead of the
    fixed-stride packed buffers."""
    d_arr = jnp.asarray(np.asarray(deltas, np.uint32))
    t_arr = jnp.asarray(np.asarray(tfs, np.uint32))
    pd, bwd = pack_ops.pack(d_arr)
    pt, bwt = pack_ops.pack(t_arr)

    n_docs = seg.n_docs
    idf = np.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))
    dl = (seg.doc_len if dl is None else dl).astype(np.float64)
    avgdl = max(dl.mean(), 1.0) if dl.size else 1.0
    doc_norm = k1 * (1.0 - b + b * dl / avgdl)
    tbs = np.concatenate([[0], np.cumsum(term_nb)])
    extra = {}
    if compact:
        # keep only what the storage codec writes: the compacted plane
        # words in 128-word rows + each block's first plane row
        wd, cd = pack_ref.compact_words(np.asarray(pd), np.asarray(bwd))
        wt, ct = pack_ref.compact_words(np.asarray(pt), np.asarray(bwt))
        extra = dict(cwords_docs=jnp.asarray(wd), coff_docs=jnp.asarray(cd),
                     cwords_tf=jnp.asarray(wt), coff_tf=jnp.asarray(ct))
        pd = pt = None
    return BlockMaxIndex(
        terms=jnp.asarray(seg.terms.astype(np.int32)),
        term_block_start=jnp.asarray(tbs.astype(np.int32)),
        idf=jnp.asarray(idf.astype(np.float32)),
        packed_docs=pd, bw_docs=bwd, packed_tf=pt, bw_tf=bwt,
        first_doc=jnp.asarray(np.asarray(first_doc, np.int32)),
        max_tf=jnp.asarray(np.asarray(max_tf, np.float32)),
        doc_norm=jnp.asarray(doc_norm.astype(np.float32)),
        n_docs=n_docs,
        max_blocks_per_term=int(np.max(term_nb)) if len(term_nb) else 1,
        k1=k1, b=b,
        min_dl=jnp.asarray(np.asarray(min_dl, np.float32)), avgdl=avgdl,
        last_doc=jnp.asarray(np.asarray(
            first_doc if last_doc is None else last_doc, np.int32)),
        **extra)


def _local_layout(seg: Segment):
    """Resolve the segment's LOCAL doc-slot layout: ``(local_docs,
    tf_stream, dl_local)`` with postings re-sorted within each term by
    slot. Natural order is the identity (zero-copy); a BP-reordered
    segment (``seg.reorder``) permutes the slot space — slot r holds the
    doc at original local index ``reorder[r]`` — so the per-term posting
    runs are re-sorted by slot rank and the doc-length vector follows
    the slots. The segment's logical arrays are untouched."""
    local_docs = np.searchsorted(seg.doc_ids, seg.docs)
    if seg.reorder is None:
        return local_docs, seg.tf, seg.doc_len
    rank_of = np.empty(seg.n_docs, np.int64)
    rank_of[seg.reorder] = np.arange(seg.n_docs)
    local_r = rank_of[local_docs]
    tix = np.repeat(np.arange(seg.n_terms), np.diff(seg.term_start))
    perm = np.lexsort((local_r, tix))   # per-term sort by new slot rank
    return local_r[perm], seg.tf[perm], seg.doc_len[seg.reorder]


def build_block_index(seg: Segment, k1: float = 0.9, b: float = 0.4,
                      compact: bool = False) -> BlockMaxIndex:
    """Block-align each term's postings and pack them — vectorized, O(P).

    Every term starts a fresh block, so block starts tile the postings
    stream contiguously: one repeat/arange pass (the CSR trick from
    ``merge.py``) enumerates them, and one scatter places each posting at
    its (block, lane) slot. Pad lanes stay 0 — identical to the scalar
    reference, where padding repeats the last doc id (delta 0) with tf 0.

    A segment carrying a BP ``reorder`` permutation gets its block layout
    built over the REORDERED local slot space (clustered similar docs →
    homogeneous per-block (max_tf, min_dl) bounds → harder MaxScore
    pruning); scores and returned absolute doc ids are unchanged — only
    which docs share a block moves. ``compact=True`` builds the fused
    decompress-and-score storage layout (see ``_finish_index``).
    """
    assert np.all(np.diff(seg.doc_ids) > 0), \
        "Segment.doc_ids must be sorted unique (np.searchsorted relies on it)"
    local_docs, tf_stream, dl_local = _local_layout(seg)
    df = np.diff(seg.term_start).astype(np.int64)
    term_nb = -(-df // BLOCK)                     # ceil: blocks per term
    nb_total = int(term_nb.sum())
    if nb_total == 0:                             # empty segment
        return _finish_index(seg, np.zeros((1, BLOCK), np.int64),
                             np.zeros((1, BLOCK), np.int64),
                             np.zeros(1, np.int64), np.zeros(1, np.int64),
                             np.zeros(1, np.int64), df, k1, b,
                             np.zeros(1, np.int64), dl=dl_local,
                             compact=compact)

    n_post = len(seg.docs)
    block_term = np.repeat(np.arange(seg.n_terms), term_nb)   # (NB,)
    nb_before = np.cumsum(term_nb) - term_nb                  # (T,)
    within = np.arange(nb_total) - nb_before[block_term]      # (NB,)
    blk_s = seg.term_start[:-1][block_term] + within * BLOCK  # (NB,) sorted,
    sizes = np.diff(np.append(blk_s, n_post))                 # tiles [0, P)
    lane = np.arange(n_post) - np.repeat(blk_s, sizes)        # (P,)
    flat_pos = np.repeat(np.arange(nb_total) * BLOCK, sizes) + lane
    d = local_docs.copy()
    d[1:] -= local_docs[:-1]
    d[blk_s] = 0                                  # first lane of each block
    deltas = np.zeros(nb_total * BLOCK, np.uint32)  # pad lanes stay 0
    deltas[flat_pos] = d
    tfs = np.zeros(nb_total * BLOCK, np.uint32)
    tfs[flat_pos] = tf_stream
    return _finish_index(seg, deltas.reshape(nb_total, BLOCK),
                         tfs.reshape(nb_total, BLOCK), local_docs[blk_s],
                         np.maximum.reduceat(tf_stream, blk_s), term_nb,
                         df, k1, b,
                         np.minimum.reduceat(dl_local[local_docs], blk_s),
                         dl=dl_local, compact=compact,
                         last_doc=local_docs[blk_s + sizes - 1])


def build_block_index_loop(seg: Segment, k1: float = 0.9, b: float = 0.4
                           ) -> BlockMaxIndex:
    """Scalar reference builder (the original per-term/per-block Python
    loop). Kept as the parity oracle for tests and the build benchmark —
    not used on any production path. Honors ``seg.reorder`` through the
    same ``_local_layout`` resolution the vectorized builder uses."""
    local_docs, tf_stream, dl_local = _local_layout(seg)
    df = np.diff(seg.term_start).astype(np.int64)
    blocks_deltas, blocks_tf, first_doc, max_tf, term_nb, min_dl, \
        last_doc = [], [], [], [], [], [], []
    for ti in range(seg.n_terms):
        s, e = int(seg.term_start[ti]), int(seg.term_start[ti + 1])
        docs = local_docs[s:e]
        tfs = tf_stream[s:e]
        nb = -(-len(docs) // BLOCK)
        term_nb.append(nb)
        for bi in range(nb):
            chunk = docs[bi * BLOCK:(bi + 1) * BLOCK]
            tchunk = tfs[bi * BLOCK:(bi + 1) * BLOCK]
            min_dl.append(dl_local[chunk].min())
            last_doc.append(chunk[-1])
            pad = BLOCK - len(chunk)
            if pad:
                chunk = np.concatenate([chunk, np.full(pad, chunk[-1])])
                tchunk = np.concatenate([tchunk, np.zeros(pad, tchunk.dtype)])
            blocks_deltas.append(np.diff(chunk, prepend=chunk[0]))
            blocks_tf.append(tchunk)
            first_doc.append(chunk[0])
            max_tf.append(tchunk.max(initial=0))
    if not blocks_deltas:
        blocks_deltas = [np.zeros(BLOCK, np.int64)]
        blocks_tf = [np.zeros(BLOCK, np.int64)]
        first_doc, max_tf, term_nb, min_dl, last_doc = \
            [0], [0], [0], [0], [0]
    return _finish_index(seg, np.stack(blocks_deltas), np.stack(blocks_tf),
                         np.asarray(first_doc), np.asarray(max_tf),
                         np.asarray(term_nb, np.int64), df, k1, b,
                         np.asarray(min_dl), dl=dl_local,
                         last_doc=np.asarray(last_doc))


# --------------------------------------------------------------------------
# readers and the multi-segment searcher
# --------------------------------------------------------------------------

def _live_term_df(seg: Segment) -> np.ndarray:
    """Per-term LIVE df: postings whose doc is tombstoned do not count
    toward collection statistics (df must describe the searchable index,
    or multi-segment idf would diverge from the compacted merge's).
    Same kernel the merge folds into its scatter — bit-identity between
    the read path and merge-time compaction by construction."""
    return live_posting_stats(seg)[1]


def _term_impacts(index: BlockMaxIndex, n_terms: int):
    """(T,) host copies of each term's competitive impact pair — best
    block-max tf and shortest doc length — the metadata the searcher's
    cross-segment ordering/skipping reads without touching the device
    (upper bounds stay valid under deletes: tombstones only remove
    postings)."""
    if n_terms == 0:
        return np.zeros(0, np.float32), np.zeros(0, np.float32)
    tbs = np.asarray(index.term_block_start)[:n_terms]
    return (np.maximum.reduceat(np.asarray(index.max_tf), tbs),
            np.minimum.reduceat(np.asarray(index.min_dl), tbs))


@dataclass
class SegmentReader:
    """One open segment: device index + doc-id map + liveness + jitted
    evaluators. The block-max index always covers the FULL postings (the
    bytes on device never change under deletes); tombstones live in the
    ``live`` mask the evaluators apply, and in the live-only statistics
    (``df_np``, ``live_doc_len``) the searcher aggregates."""

    seg: Segment
    index: BlockMaxIndex
    doc_map: jnp.ndarray          # (D,) local -> absolute doc id
    terms_np: np.ndarray          # host copies for global-df lookups
    df_np: np.ndarray             # (T,) LIVE df per term
    nb_np: np.ndarray             # (T,) blocks per term
    term_max_tf_np: np.ndarray = None  # (T,) best block-max tf per term
    term_min_dl_np: np.ndarray = None  # (T,) shortest doc length per term
    live: object = None           # (D,) bool device mask; None = no deletes
    live_doc_len: np.ndarray = None  # host doc lengths of live docs only
    doc_len_local: np.ndarray = None  # (D,) doc lengths in LOCAL slot order
    _fns: dict = field(default_factory=dict)

    @classmethod
    def open(cls, seg: Segment, k1: float = 0.9, b: float = 0.4,
             compact: bool = False, device=None) -> "SegmentReader":
        """Build the reader's index on ``device`` (None: JAX's default
        device now) and commit every device array to it: a jitted
        evaluator runs where its committed arguments live, so the reader
        serves from that device whichever thread calls it."""
        if device is None:
            device = next(iter(jnp.zeros(()).devices()))
        df_full = np.diff(seg.term_start).astype(np.int64)
        with jax.default_device(device):
            index = build_block_index(seg, k1, b, compact=compact)
        index = replace(index, **{
            f.name: jax.device_put(getattr(index, f.name), device)
            for f in fields(index)
            if isinstance(getattr(index, f.name), jax.Array)})
        tmax, tmin = _term_impacts(index, seg.n_terms)
        # everything indexed by LOCAL doc slot follows the BP permutation
        # when the segment carries one; the logical arrays stay natural
        r = seg.reorder
        doc_ids_local = seg.doc_ids if r is None else seg.doc_ids[r]
        live_local = None
        if seg.has_deletes:
            live_np = ~seg.deletes
            live_local = jax.device_put(live_np if r is None
                                        else live_np[r], device)
        return cls(seg=seg, index=index,
                   doc_map=jax.device_put(doc_ids_local.astype(np.int32),
                                          device),
                   terms_np=np.asarray(seg.terms),
                   df_np=_live_term_df(seg),
                   nb_np=-(-df_full // BLOCK),
                   term_max_tf_np=tmax, term_min_dl_np=tmin,
                   live=live_local,
                   live_doc_len=(seg.doc_len[~seg.deletes]
                                 if seg.has_deletes else seg.doc_len),
                   doc_len_local=(seg.doc_len if r is None
                                  else seg.doc_len[r]))

    def reopen(self, seg: Segment) -> "SegmentReader":
        """Same postings core (``seg.base_id == self.seg.base_id``), new
        tombstone bitmap: shares the packed device index, the doc map AND
        the compiled evaluator cache (liveness is an argument of the
        masked evaluators, not baked into their traces) — a delete costs
        one O(P) host pass for live stats instead of an index rebuild."""
        assert seg.base_id == self.seg.base_id, "reopen needs the same core"
        live_local = None
        if seg.has_deletes:
            live_np = ~seg.deletes
            live_local = jax.device_put(
                live_np if seg.reorder is None else live_np[seg.reorder],
                self.device)
        return SegmentReader(
            seg=seg, index=self.index, doc_map=self.doc_map,
            terms_np=self.terms_np, df_np=_live_term_df(seg),
            nb_np=self.nb_np, term_max_tf_np=self.term_max_tf_np,
            term_min_dl_np=self.term_min_dl_np,
            live=live_local,
            live_doc_len=(seg.doc_len[~seg.deletes] if seg.has_deletes
                          else seg.doc_len),
            doc_len_local=self.doc_len_local,
            _fns=self._fns)

    @property
    def device(self):
        """The device this reader's index is committed to."""
        return next(iter(self.doc_map.devices()))

    @property
    def seg_id(self) -> int:
        return self.seg.seg_id

    @property
    def n_docs(self) -> int:
        return self.seg.n_docs

    @property
    def live_docs(self) -> int:
        return self.seg.live_doc_count

    def query_max_blocks(self, q: np.ndarray) -> int:
        """Exact max blocks-per-term over the query's terms, rounded up to
        a power of two (so compiles are bounded at log2(MB) shape buckets).
        The segment-wide max is a gross over-estimate for typical queries —
        one huge term forces MB on everyone — and candidate-grid cost is
        linear in the window, so right-sizing it per query batch is the
        difference between scoring 128 lanes/term and 128*MB."""
        t = self.terms_np
        if t.size == 0:
            return 1
        rows = np.clip(np.searchsorted(t, q), 0, t.size - 1)
        nb = np.where(t[rows] == q, self.nb_np[rows], 1)
        need = int(nb.max(initial=1))
        return min(1 << (need - 1).bit_length(),
                   max(self.index.max_blocks_per_term, 1))

    def query_max_ub(self, q2d: np.ndarray, idf2d: np.ndarray,
                     avgdl: float = 1.0) -> np.ndarray:
        """(B,) best POSSIBLE score this segment can give each query: the
        sum over query terms of the term's best impact bound (max tf +
        shortest doc under ``avgdl``), from host metadata only. The
        searcher visits segments in descending order of this bound and
        skips a segment outright once the shared theta exceeds it (no doc
        inside can beat the running top-k)."""
        t = self.terms_np
        q = np.asarray(q2d)
        if t.size == 0:
            return np.zeros(q.shape[0], np.float64)
        rows = np.clip(np.searchsorted(t, q), 0, t.size - 1)
        found = t[rows] == q
        mt = np.where(found, self.term_max_tf_np[rows], 0.0)
        k1, b = self.index.k1, self.index.b
        norm = k1 * (1.0 - b) \
            + k1 * b * np.where(found, self.term_min_dl_np[rows], 0.0) / avgdl
        ub = np.where(mt > 0,
                      np.asarray(idf2d, np.float64) * (k1 + 1.0)
                      * mt / (mt + norm), 0.0)
        return ub.sum(axis=-1)

    def topk_fn(self, k: int, max_blocks: int, batched: bool = False):
        """Jitted dense-exhaustive ``(q, idf_q, doc_norm[, live]) ->
        (scores, abs doc ids)`` — the baseline every pruned result is
        asserted against, and the serving path when ``prune=False``.

        EVERYTHING arrives as arguments (not baked into the trace): the
        index arrays, the doc map, idf/doc_norm, and (masked variant) the
        (D,) live mask — so a refresh that only changes stats or bitmaps
        reuses the compiled fn, and readers over same-SHAPED segments
        share one compiled evaluator through the process-global cache
        (see ``_shared_evaluator``). The dense path computes every
        candidate lane; actual block skipping lives in the compacted
        pruned path (``topk_pruned``)."""
        masked = self.live is not None
        key = (k, max_blocks, batched, masked)
        if key not in self._fns:
            def build(statics):
                def single(arrs, doc_map, q, idf_q, doc_norm, live):
                    index = _rebuild_index(arrs, *statics)
                    vals, ids, _ = bm25_topk_dense(
                        index, q, k, prune=False, idf_q=idf_q,
                        doc_norm=doc_norm, max_blocks=max_blocks, live=live)
                    return vals, doc_map[ids]

                if masked:
                    fn = jax.vmap(single,
                                  in_axes=(None, None, 0, 0, None, None)) \
                        if batched else single
                else:
                    def nolive(arrs, doc_map, q, idf_q, doc_norm):
                        return single(arrs, doc_map, q, idf_q, doc_norm,
                                      None)
                    fn = jax.vmap(nolive,
                                  in_axes=(None, None, 0, 0, None)) \
                        if batched else nolive
                return jax.jit(fn)

            fn, cached = _shared_evaluator(
                ("dense", k, max_blocks, batched, masked), self.index,
                build)
            _count_eval_hit(cached)
            self._fns[key] = fn
        return self._fns[key]

    def topk(self, q, idf_q, doc_norm, k: int, max_blocks: int,
             batched: bool = False):
        """Dense-exhaustive top-k on this segment, masking tombstones when
        the segment has any (the searcher's ``prune=False`` entry point)."""
        fn = self.topk_fn(k, max_blocks, batched)
        arrs = _index_arrays(self.index)
        if self.live is not None:
            return fn(arrs, self.doc_map, q, idf_q, doc_norm, self.live)
        return fn(arrs, self.doc_map, q, idf_q, doc_norm)

    def _pruned_fns(self, k: int, max_blocks: int, n_rows: int,
                    midgrid: bool = False):
        """Cached jitted device stages of the compacted pruned path: the
        vmapped metadata pass, the batch-flat compacted scorer, and (when
        ``midgrid``) the theta-tightening scorer variant. Each is one
        compiled function per (kind, statics, shape signature) in the
        process-global cache — jax's shape cache handles the
        (log2-bounded, bucket-padded) survivor shapes. The decision
        between them (``probe_pick``, ``prune_decide``,
        ``compact_survivors``) takes no index arrays: one jitted function
        each, keyed on the metadata's shapes and shared by every
        segment."""
        mkey = ("meta", max_blocks)
        if mkey not in self._fns:
            def build(statics):
                def meta(arrs, q2d, idf2d, avgdl):
                    index = _rebuild_index(arrs, *statics)
                    return jax.vmap(
                        lambda q, f: prune_candidates(index, q, f,
                                                      max_blocks, avgdl)
                    )(q2d, idf2d)
                return jax.jit(meta)

            fn, cached = _shared_evaluator(mkey, self.index, build)
            _count_eval_hit(cached)
            self._fns[mkey] = fn
        masked = self.live is not None
        skey = ("scorer", k, n_rows, masked)
        if skey not in self._fns:
            def build(statics):
                def score(arrs, doc_map, ci, cf, ca, cr, doc_norm, live):
                    index = _rebuild_index(arrs, *statics)
                    vals, ids = score_survivors(index, ci, cf, ca, cr,
                                                n_rows, k, doc_norm, live)
                    return vals, doc_map[ids]

                if masked:
                    return jax.jit(score)

                def nolive(arrs, doc_map, ci, cf, ca, cr, doc_norm):
                    return score(arrs, doc_map, ci, cf, ca, cr, doc_norm,
                                 None)
                return jax.jit(nolive)

            fn, cached = _shared_evaluator(("scorer", k, n_rows, masked),
                                           self.index, build)
            _count_eval_hit(cached)
            self._fns[skey] = fn
        mid = None
        if midgrid:
            dkey = ("midscorer", k, n_rows)
            if dkey not in self._fns:
                def build(statics):
                    def score(arrs, doc_map, ci, cf, ca, cr, cu, th,
                              doc_norm):
                        index = _rebuild_index(arrs, *statics)
                        vals, ids, nskip = score_survivors_midgrid(
                            index, ci, cf, ca, cr, cu, th, n_rows, k,
                            doc_norm)
                        return vals, doc_map[ids], nskip
                    return jax.jit(score)

                fn, cached = _shared_evaluator(dkey, self.index, build)
                _count_eval_hit(cached)
                self._fns[dkey] = fn
            mid = self._fns[dkey]
        return self._fns[mkey], self._fns[skey], mid

    def topk_pruned(self, q2d, idf2d, doc_norm, k: int, max_blocks: int,
                    theta0=None, avgdl=None, bmw: bool = True,
                    midgrid: bool = True):
        """Compacted pruned top-k over a (B, Q) batch: metadata pass ->
        device BMW overlap-bound test (``bmw=False``: term-level MaxScore)
        at max(phase-1 theta, ``theta0``) -> device survivor compaction ->
        compacted survivor scoring,
        through the midgrid theta-tightening kernel when its gates hold
        (``midgrid`` requested, no tombstones, fixed-stride layout, k
        within the in-kernel fold's budget, batch rows within the
        carry's 128 lanes). ``avgdl`` must be the mean doc length the
        passed ``doc_norm`` was built from (the searcher passes its
        collection-global snapshot value) — it tightens the impact
        bounds; None keeps the stats-independent safe floor. Returns
        ``(vals (B, k), abs doc ids (B, k), PruneStats)`` — exactly the
        dense path's results, at survivor-proportional cost."""
        n_rows = int(q2d.shape[0])
        use_mid = (midgrid and self.live is None and not self.index.compact
                   and k <= MIDGRID_MAX_K and n_rows <= BLOCK)
        meta_j, scorer, mid = self._pruned_fns(k, max_blocks, n_rows,
                                               use_mid)
        arrs = _index_arrays(self.index)
        doc_map = self.doc_map
        a = None if avgdl is None else jnp.float32(avgdl)
        meta = lambda q2, f2: meta_j(arrs, q2, f2, a)
        live = self.live
        if live is not None:
            def scorer_for(_n):
                return lambda ci, cf, ca, cr: scorer(
                    arrs, doc_map, ci, cf, ca, cr, doc_norm, live)
        else:
            def scorer_for(_n):
                return lambda ci, cf, ca, cr: scorer(
                    arrs, doc_map, ci, cf, ca, cr, doc_norm)
        scorer_mid_for = None
        if use_mid:
            def scorer_mid_for(_n):
                return lambda ci, cf, ca, cr, cu, th: mid(
                    arrs, doc_map, ci, cf, ca, cr, cu, th, doc_norm)
        return pruned_eval(meta, scorer_for,
                           jnp.asarray(q2d, jnp.int32), jnp.asarray(idf2d),
                           theta0=theta0, bmw=bmw,
                           scorer_mid_for=scorer_mid_for)


@dataclass
class IndexSearcher:
    """Point-in-time searchable view over a set of live segments.

    Per-segment evaluation runs under collection-global statistics
    computed from LIVE docs only: df is summed across segments (disjoint
    doc spaces -> live df adds), avgdl is the mean length of live docs.
    Each live doc is in exactly one segment, so its score is identical to
    what the force-merged COMPACTED index would give it, and a merge of
    per-segment top-k equals global top-k; tombstoned docs are masked
    inside the evaluators and never surface.

    ``prune=True`` (default) serves through the compacted pruned path
    with cross-segment threshold sharing; ``prune=False`` serves the
    dense exhaustive baseline (identical results — asserted in tests).
    ``prune_stats`` accumulates the per-batch pruning counters across the
    searcher's lifetime (the scheduler and ``envelope_report`` read it) —
    the one mutable part of an otherwise-immutable snapshot, so its
    accumulation is serialized under a lock (serving threads share one
    searcher; readers of the counters tolerate momentarily-torn values).
    """

    readers: list
    k1: float = 0.9
    b: float = 0.4
    prune: bool = True
    bmw: bool = True       # doc-range-overlap (BMW) bound; False: MaxScore
    midgrid: bool = True   # in-grid theta tightening where its gates hold
    n_docs: int = 0                # LIVE docs in the snapshot
    avgdl: float = 1.0
    # degraded serving (fault-tolerance layer): True when the snapshot
    # was recovered minus quarantined segments — results are correct over
    # the surviving docs, but ``missing_docs`` committed docs are absent
    degraded: bool = False
    missing_docs: int = 0
    quarantined: tuple = ()        # quarantined segment base names
    # snapshot identity for result caching: two searchers with the same
    # nonzero generation serve bit-identical results for every query (the
    # ReaderCache assigns one per distinct (seg_ids, quarantine) state,
    # from a process-global counter so fleets of caches never collide).
    # 0 = unkeyed snapshot — result caches must treat it as uncacheable.
    generation: int = 0
    # collection statistics imposed from OUTSIDE this snapshot (fleet
    # serving): an object with ``n_docs`` / ``avgdl`` / ``df_terms`` /
    # ``df_table`` covering the UNION of all shards. Doc spaces across
    # shards are disjoint, so the union stats are exactly what a
    # single-index searcher over the union corpus computes — per-doc
    # scores under them are bit-identical to that oracle's (doc lengths
    # and dfs are integers, so the shared sums are exact in float64
    # regardless of how they were grouped).
    collection_stats: object = None
    prune_stats: PruneStats = None
    _doc_norms: list = None
    _df_terms: np.ndarray = None   # (U,) sorted union of segment terms
    _df_table: np.ndarray = None   # (U,) collection-wide LIVE df per term
    _stats_lock: threading.Lock = None

    def __post_init__(self):
        self.prune_stats = PruneStats()
        self._stats_lock = threading.Lock()
        dls = [r.live_doc_len for r in self.readers]
        all_dl = (np.concatenate(dls).astype(np.float64) if dls
                  else np.zeros(0, np.float64))
        self.n_docs = int(all_dl.size)
        self.avgdl = max(all_dl.mean(), 1.0) if all_dl.size else 1.0
        if self.collection_stats is not None:
            self.n_docs = int(self.collection_stats.n_docs)
            self.avgdl = float(self.collection_stats.avgdl)
        # norms are indexed by LOCAL doc slot at scoring time, so a
        # BP-reordered segment needs the permuted doc-length vector
        self._doc_norms = [
            jnp.asarray((self.k1 * (1.0 - self.b + self.b *
                         (r.doc_len_local if r.doc_len_local is not None
                          else r.seg.doc_len).astype(np.float64)
                         / self.avgdl)
                         ).astype(np.float32))
            for r in self.readers]
        # merged (term, df) table, built once per snapshot: doc spaces are
        # disjoint, so collection df is the plain sum of per-segment dfs.
        # global_idf then costs one searchsorted per query batch instead of
        # one per (reader, query).
        if self.collection_stats is not None:
            self._df_terms = np.asarray(self.collection_stats.df_terms,
                                        np.int64)
            self._df_table = np.asarray(self.collection_stats.df_table,
                                        np.int64)
        elif self.readers:
            all_t = np.concatenate([r.terms_np for r in self.readers])
            all_df = np.concatenate([r.df_np for r in self.readers])
            self._df_terms, inv = np.unique(all_t, return_inverse=True)
            self._df_table = np.zeros(self._df_terms.size, np.int64)
            np.add.at(self._df_table, inv, all_df)
        else:
            self._df_terms = np.zeros(0, np.int64)
            self._df_table = np.zeros(0, np.int64)

    @property
    def n_segments(self) -> int:
        return len(self.readers)

    def with_stats(self, stats) -> "IndexSearcher":
        """This snapshot's readers served under externally-imposed
        collection statistics (see ``collection_stats``). The fleet layer
        wraps each shard's searcher with the union stats so per-shard
        evaluation matches the union-index oracle score-for-score."""
        return IndexSearcher(readers=self.readers, k1=self.k1, b=self.b,
                             prune=self.prune, bmw=self.bmw,
                             midgrid=self.midgrid, degraded=self.degraded,
                             missing_docs=self.missing_docs,
                             quarantined=self.quarantined,
                             collection_stats=stats)
        # generation stays 0: the imposed stats change scores, so this
        # snapshot's key no longer determines the wrapped results (the
        # fleet layer keys its caches on its own all-shard generation)

    def global_idf(self, q_terms: np.ndarray) -> np.ndarray:
        """Collection-wide idf for ``q_terms`` (any shape): one lookup in
        the precomputed merged (term, df) table, then the same idf formula
        the single-segment builder bakes in. Terms absent from every
        segment (including -1 query padding) get df 0."""
        q = np.asarray(q_terms, np.int64)
        t = self._df_terms
        if t.size == 0:
            df = np.zeros(q.shape, np.int64)
        else:
            rows = np.clip(np.searchsorted(t, q), 0, t.size - 1)
            df = np.where(t[rows] == q, self._df_table[rows], 0)
        return np.log(1.0 + (self.n_docs - df + 0.5) / (df + 0.5)
                      ).astype(np.float32)

    def _empty(self, shape_prefix, k):
        return (jnp.zeros(shape_prefix + (k,), jnp.float32),
                jnp.full(shape_prefix + (k,), -1, jnp.int32))

    def query_max_ub(self, q2d: np.ndarray) -> np.ndarray:
        """(B,) best POSSIBLE score this snapshot can give each query —
        the max over live segments of the per-segment impact bound, under
        this searcher's (possibly fleet-imposed) collection stats. The
        fleet layer visits SHARDS in descending order of this bound and
        skips a shard wholesale once the cross-shard theta exceeds it,
        exactly as ``_search_pruned`` does with segments."""
        q = np.asarray(q2d)
        idf = self.global_idf(q)
        ubs = [r.query_max_ub(q, idf, self.avgdl) for r in self.readers
               if r.live_docs > 0 and r.terms_np.size > 0]
        if not ubs:
            return np.zeros(q.shape[0], np.float64)
        return np.max(np.stack(ubs), axis=0)

    def _search_pruned(self, q2d: np.ndarray, k: int, theta0=None):
        """Shared pruned evaluation over a (B, Q) batch with cross-segment
        threshold sharing: readers are visited in descending best-possible
        -score order; the running global k-th score (a valid lower bound
        on the final k-th — scores only join the pool, never leave) seeds
        each later segment's theta, and a segment whose best possible
        score is strictly below the bound for every query is skipped
        without touching the device at all.

        ``theta0`` (optional, (B,) or scalar) seeds the bound from OUTSIDE
        the snapshot — cross-shard sharing: the caller asserts k results
        with score >= theta0 are already secured on other shards, so a
        segment (or the whole snapshot) below it can be skipped before any
        local results exist. Same contract as the per-segment ``theta0``:
        results strictly above the seed are exact; docs at or below it may
        be dropped, but >= k better ones exist elsewhere by assertion."""
        B = q2d.shape[0]
        stats = PruneStats(queries=B, batches=1)
        with span("search.plan"):
            idf = self.global_idf(q2d)
            live = [(r, dn) for r, dn in zip(self.readers, self._doc_norms)
                    if min(k, r.live_docs) > 0 and r.terms_np.size > 0]
            seg_ub = [r.query_max_ub(q2d, idf, self.avgdl)
                      for r, _ in live]
            order = np.argsort([-float(u.sum()) for u in seg_ub],
                               kind="stable")
        ext_theta = theta0 is not None
        theta0 = (np.zeros(B, np.float64) if theta0 is None else
                  np.array(np.broadcast_to(
                      np.asarray(theta0, np.float64), (B,))))
        running = None  # (B, <=k) best values seen so far, O(S*k) upkeep
        parts_v, parts_i = [], []
        for oi in order:
            r, dn = live[oi]
            k_eff = min(k, r.live_docs)
            if (ext_theta or (running is not None
                              and running.shape[1] >= k)) \
                    and bool(np.all(seg_ub[oi] < theta0)):
                stats.segments_skipped += 1
                continue  # nothing inside can beat the running top-k
            with span("search.segment", seg=r.seg_id):
                mb = r.query_max_blocks(q2d)
                v, i, st = r.topk_pruned(q2d, idf, dn, k_eff, mb,
                                         theta0=theta0, avgdl=self.avgdl,
                                         bmw=self.bmw, midgrid=self.midgrid)
                stats.add(st)
                parts_v.append(v)
                parts_i.append(i)
                running = v if running is None \
                    else np.concatenate([running, v], axis=1)
                if running.shape[1] > k:
                    running = -np.partition(-running, k - 1, axis=1)[:, :k]
                if running.shape[1] >= k:
                    theta0 = np.maximum(theta0, running.min(axis=1))
        with self._stats_lock:
            self.prune_stats.add(stats)
        if not parts_v:
            return self._empty((B,), k)
        with span("search.merge"):
            vals = jnp.asarray(np.concatenate(parts_v, axis=1))
            ids = jnp.asarray(np.concatenate(parts_i, axis=1))
            kk = min(k, vals.shape[1])
            top_v, pos = jax.lax.top_k(vals, kk)
            top_i = jnp.take_along_axis(ids, pos, axis=1)
            if kk < k:
                top_v = jnp.pad(top_v, ((0, 0), (0, k - kk)))
                top_i = jnp.pad(top_i, ((0, 0), (0, k - kk)),
                                constant_values=-1)
            return jax.device_get((top_v, top_i))

    def search(self, q_terms, k: int = 10):
        """Top-k over every live segment; returns (scores (k,), doc_ids (k,))
        with absolute doc ids. Results are identical to exhaustive
        evaluation over the force-merged compacted segment (asserted in
        tests). Per-segment k is capped at the LIVE doc count, so a
        reader's top-k can never be forced to dip into its tombstoned
        (masked, score -1) docs."""
        q = np.asarray(q_terms)
        if self.prune:
            v, i = self._search_pruned(q[None], k)
            return v[0], i[0]
        idf = jnp.asarray(self.global_idf(q))
        qj = jnp.asarray(q, jnp.int32)
        parts_v, parts_i = [], []
        for r, dn in zip(self.readers, self._doc_norms):
            k_eff = min(k, r.live_docs)
            if k_eff <= 0 or r.terms_np.size == 0:
                continue  # nothing live (or no postings): contributes 0
            v, i = r.topk(qj, idf, dn, k_eff, r.query_max_blocks(q))
            parts_v.append(v)
            parts_i.append(i)
        if not parts_v:
            return self._empty((), k)
        vals = jnp.concatenate(parts_v)
        ids = jnp.concatenate(parts_i)
        kk = min(k, vals.shape[0])
        top_v, pos = jax.lax.top_k(vals, kk)
        top_i = ids[pos]
        if kk < k:
            top_v = jnp.pad(top_v, (0, k - kk))
            top_i = jnp.pad(top_i, (0, k - kk), constant_values=-1)
        return top_v, top_i

    def search_batched(self, q_batch, k: int = 10, theta0=None):
        """Fixed-shape batched search: ``q_batch`` is (B, Q) int32, queries
        right-padded with -1 (absent everywhere -> contributes nothing).
        Returns (scores (B, k), doc_ids (B, k)). With pruning, each
        segment evaluates the whole batch through one metadata pass + one
        compacted scorer call (survivors padded to a shared power-of-two
        bucket across the batch, so compiled shapes stay bounded).
        ``theta0`` seeds the pruning threshold from outside the snapshot
        (cross-shard bound sharing — see ``_search_pruned``); the dense
        exhaustive path ignores it (its results are exact regardless)."""
        q = np.asarray(q_batch)
        if self.prune:
            return self._search_pruned(q, k, theta0=theta0)
        B = q.shape[0]
        idf = jnp.asarray(self.global_idf(q))
        qj = jnp.asarray(q, jnp.int32)
        parts_v, parts_i = [], []
        for r, dn in zip(self.readers, self._doc_norms):
            k_eff = min(k, r.live_docs)
            if k_eff <= 0 or r.terms_np.size == 0:
                continue  # nothing live (or no postings): contributes 0
            mb = r.query_max_blocks(q)
            v, i = r.topk(qj, idf, dn, k_eff, mb, batched=True)
            parts_v.append(v)
            parts_i.append(i)
        if not parts_v:
            return self._empty((B,), k)
        vals = jnp.concatenate(parts_v, axis=1)
        ids = jnp.concatenate(parts_i, axis=1)
        kk = min(k, vals.shape[1])
        top_v, pos = jax.lax.top_k(vals, kk)
        top_i = jnp.take_along_axis(ids, pos, axis=1)
        if kk < k:
            top_v = jnp.pad(top_v, ((0, 0), (0, k - kk)))
            top_i = jnp.pad(top_i, ((0, 0), (0, k - kk)), constant_values=-1)
        return top_v, top_i


# process-global searcher-generation source: every distinct snapshot state
# any ReaderCache serves gets a unique nonzero id, so result caches keyed
# by generation can never collide across indexes, shards, or replicas
_GENERATIONS = itertools.count(1)


@dataclass
class ReaderCache:
    """Reader cache keyed by segment identity (``Segment.seg_id``).

    ``refresh(segs)`` returns a searcher over exactly ``segs``, reusing
    cached readers for segments seen before and evicting readers whose
    segments left the live set (merged away). After a merge cascade only
    the cascade's *output* segment needs a reader build; after a delete
    (same ``base_id``, new bitmap) the cached reader is REOPENED — the
    packed index, doc map and compiled evaluators carry over and only the
    live statistics are recomputed (``reopens`` counts these).

    Thread-safe under the concurrent merge scheduler: ``segs`` is an
    atomic ``live_segments()`` snapshot of immutable segments, so reader
    builds never race with the merge that produced a segment; the internal
    lock only serializes concurrent ``refresh`` callers mutating the cache
    dict and its counters.
    """

    k1: float = 0.9
    b: float = 0.4
    prune: bool = True   # searchers serve the compacted pruned path
    bmw: bool = True     # BMW doc-range-overlap bounds (False: MaxScore)
    midgrid: bool = True  # in-grid theta tightening where gates hold
    compact: bool = False  # fused decompress-and-score index layout
    device: object = None  # jax.Device readers live on; None = JAX's
    #                        default device when each reader is built
    builds: int = 0
    hits: int = 0
    reopens: int = 0   # bitmap-only reader swaps (shared core)
    evictions: int = 0
    _readers: dict = field(default_factory=dict)
    _max_seen: int = -1  # newest seg_id ever installed (monotonic)
    # searcher-generation state: the generation bumps (fresh id from the
    # process-global counter) exactly when the served snapshot's identity
    # — live seg_ids (seg_id changes per delete generation) + quarantine
    # state — changes, so equal generations imply bit-identical results
    _gen_key: tuple = None
    _generation: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    def refresh(self, segs: list, recovery=None) -> IndexSearcher:
        """``recovery`` (a ``storage.RecoveryInfo`` or any object with
        ``quarantined``/``missing_docs``) marks the returned searcher
        degraded: it serves ``segs`` while reporting what is missing."""
        with self._lock:
            have = dict(self._readers)
        # build missing readers OUTSIDE the lock: a refresh that is all
        # cache hits must never wait behind another thread's cold build
        # (segments are immutable, so the worst case is a duplicate build
        # and one copy wins the swap below). A miss whose postings core is
        # already open (a delete generation of a cached segment) reopens
        # that reader instead of rebuilding the device index.
        by_base = {r.seg.base_id: r for r in have.values()}
        fresh, n_reopened = {}, 0
        for seg in segs:
            if seg.seg_id in have:
                continue
            core = by_base.get(seg.base_id)
            if core is not None:
                fresh[seg.seg_id] = core.reopen(seg)
                n_reopened += 1
            else:
                fresh[seg.seg_id] = SegmentReader.open(
                    seg, self.k1, self.b, compact=self.compact,
                    device=self.device)
        with self._lock:
            self.builds += len(fresh) - n_reopened
            self.reopens += n_reopened
            live, readers = {}, []
            for seg in segs:
                r = self._readers.get(seg.seg_id)
                if r is None:
                    # fall back to ``have`` for a reader another refresh
                    # evicted between our snapshot and this swap
                    r = fresh.get(seg.seg_id) or have.get(seg.seg_id)
                else:
                    self.hits += 1
                live[seg.seg_id] = r
                readers.append(r)
            # install only if this snapshot is not older than what the
            # cache already holds: seg_ids are monotonic and segments only
            # leave the live set by merging into a *newer* segment, so a
            # stale snapshot must not evict newer readers (its searcher is
            # still returned — correctness is per-snapshot either way)
            snap_max = max(live, default=-1)
            if snap_max >= self._max_seen:
                self._max_seen = snap_max
                self.evictions += len(set(self._readers) - set(live))
                self._readers = live
        quarantined = tuple(sorted(getattr(recovery, "quarantined", ())
                                   or ()))
        missing = int(getattr(recovery, "missing_docs", 0) or 0)
        gen_key = (tuple(sorted(s.seg_id for s in segs)), quarantined,
                   missing)
        with self._lock:
            if gen_key != self._gen_key:
                self._gen_key = gen_key
                self._generation = next(_GENERATIONS)
            generation = self._generation
        return IndexSearcher(readers=readers, k1=self.k1, b=self.b,
                             prune=self.prune, bmw=self.bmw,
                             midgrid=self.midgrid,
                             degraded=bool(quarantined),
                             missing_docs=missing,
                             quarantined=quarantined,
                             generation=generation)
