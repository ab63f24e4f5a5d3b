"""Plain references, computed from the raw token buffers alone.

They share no code and no data structure with the program: a reference
sees only the documents the benchmark made from the seed and fed, in
feeding order (doc id = position in the stream). Doc length is the
count of non-pad tokens, term frequency the count of a term's tokens in
a document.

- BM25 (serving): Lucene/Anserini BM25 over every fed document,
  ``idf = ln(1 + (N - df + 0.5) / (df + 0.5))`` and
  ``idf * (k1 + 1) * tf / (tf + k1 * (1 - b + b * dl / avgdl))``
  summed over the query's terms, in float64. ``bm25_scores(dtype=...)``
  repeats the arithmetic in a lower precision: that is the control.
- Inversion (ingest): a fingerprint of each document's postings
  ``(term, tf)`` and positions ``(term, position)``, summed per document
  exactly (see ``_chunks``), from the tokens on one side and from the
  recovered segments on the other.
"""
from __future__ import annotations

import numpy as np

# -------------------------------------------------------------------------
# BM25 over the raw collection
# -------------------------------------------------------------------------


class TokenCollection:
    """The fed documents, ``batches[i]`` holding doc ids
    ``sum(len(batches[:i])) ...``."""

    def __init__(self, batches: list):
        self.batches = batches
        self.starts = np.cumsum([0] + [b.shape[0] for b in batches])
        self.dl = np.concatenate([(b > 0).sum(axis=1) for b in batches]
                                 ).astype(np.float64)
        self.n_docs = int(self.dl.size)
        self.avgdl = max(float(self.dl.mean()), 1.0)

    def postings(self, terms, vocab: int) -> dict:
        """``{term: (doc ids ascending, tf)}`` for every term of ``terms``
        (absent terms map to empty arrays). One pass over the tokens."""
        want = np.zeros(vocab + 1, bool)
        terms = np.unique(np.asarray(terms, np.int64))
        terms = terms[(terms > 0) & (terms <= vocab)]
        want[terms] = True
        keys = []
        for start, b in zip(self.starts, self.batches):
            r, c = np.nonzero(want[b])
            keys.append((b[r, c].astype(np.int64) << 32) | (r + start))
        u, tf = np.unique(np.concatenate(keys), return_counts=True)
        t, d = u >> 32, u & 0xFFFFFFFF
        cut = np.searchsorted(t, terms)
        end = np.searchsorted(t, terms, side="right")
        out = {int(x): (d[lo:hi], tf[lo:hi])
               for x, lo, hi in zip(terms, cut, end)}
        return out

    def bm25_scores(self, q, post: dict, k1: float, b: float,
                    dtype=np.float64):
        """``(doc ids ascending, scores)`` of every doc matching a term of
        ``q``. With a ``dtype`` below float64 every product, quotient and
        sum is rounded to it (the lower-precision control)."""
        cast = np.dtype(dtype).type
        ids = [np.zeros(0, np.int64)]
        parts = []
        for t in np.asarray(q, np.int64):
            d, tf = post.get(int(t), (np.zeros(0, np.int64),) * 2)
            if d.size == 0:
                continue
            df = d.size
            idf = np.log(1.0 + (self.n_docs - df + 0.5) / (df + 0.5))
            tf_ = tf.astype(dtype)
            norm = (cast(k1) * (cast(1.0 - b) + cast(b) * (
                self.dl[d].astype(dtype) / cast(self.avgdl)))).astype(dtype)
            s = (cast(idf) * cast(k1 + 1.0) * tf_ / (tf_ + norm)).astype(dtype)
            ids.append(d)
            parts.append(s)
        u, inv = np.unique(np.concatenate(ids), return_inverse=True)
        acc = np.zeros(u.size, dtype)
        for d, s in zip(ids[1:], parts):
            j = np.searchsorted(u, d)
            acc[j] = (acc[j] + s).astype(dtype)
        return u, acc.astype(np.float64)


def topk(ids: np.ndarray, scores: np.ndarray, k: int):
    """Top-k (values descending, ids), padded with (0, -1)."""
    order = np.argsort(-scores, kind="stable")[:k]
    v = np.zeros(k)
    i = np.full(k, -1, np.int64)
    v[:order.size] = scores[order]
    i[:order.size] = ids[order]
    return v, i


def compare_topk(vals, doc_ids, ref_ids, ref_scores, k: int):
    """Gaps of one served top-k against the reference, both relative:
    ``value_gap``, the widest gap between the served value and the
    reference's at each rank; ``id_gap``, the widest gap between a served
    value and the reference score of the doc served with it (a doc the
    reference does not match at all scores 0, a gap of 1)."""
    want, _ = topk(ref_ids, ref_scores, k)
    vals = np.asarray(vals, np.float64)[:k]
    den = np.maximum(np.maximum(np.abs(want), np.abs(vals)), 1e-30)
    value_gap = float(np.max(np.abs(vals - want) / den, initial=0.0))
    id_gap = 0.0
    for d, v in zip(np.asarray(doc_ids)[:k], vals):
        if v <= 0:
            continue
        j = int(np.searchsorted(ref_ids, d))
        own = ref_scores[j] if j < ref_ids.size and ref_ids[j] == d else 0.0
        id_gap = max(id_gap, float(abs(own - v) / max(abs(own), abs(v))))
    return value_gap, id_gap


# -------------------------------------------------------------------------
# inversion fingerprints
# -------------------------------------------------------------------------

_M1, _M2 = np.uint64(0xBF58476D1CE4E5B9), np.uint64(0x94D049BB133111EB)
_A, _B, _C = (np.uint64(0x9E3779B97F4A7C15), np.uint64(0xD6E8FEB86659FD93),
              np.uint64(0xA0761D6478BD642F))
N_CHUNKS = 3


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser over uint64 (wrapping arithmetic)."""
    x = x ^ (x >> np.uint64(30))
    x = x * _M1
    x = x ^ (x >> np.uint64(27))
    x = x * _M2
    return x ^ (x >> np.uint64(31))


def _chunks(h: np.ndarray):
    """Three 20-bit chunks of a 64-bit hash, as float64: a per-document
    sum of at most 2^10 entries times tf <= 2^10 stays below 2^53, so
    ``np.bincount`` sums them exactly."""
    return [((h >> np.uint64(20 * j)) & np.uint64(0xFFFFF)).astype(np.float64)
            for j in range(N_CHUNKS)]


def _posting_hash(term, doc):
    return _mix(term.astype(np.uint64) * _A + doc.astype(np.uint64) * _B)


def _position_hash(term, doc, pos):
    return _mix(term.astype(np.uint64) * _A + doc.astype(np.uint64) * _B
                + (pos.astype(np.uint64) + np.uint64(1)) * _C)


class Fingerprints:
    """Per-document (length, postings fingerprint, positions fingerprint)
    over a contiguous doc-id range ``[lo, lo + n)``."""

    def __init__(self, lo: int, n: int):
        self.lo, self.n = int(lo), int(n)
        self.seen = np.zeros(n, bool)
        self.dl = np.zeros(n, np.int64)
        self.post = np.zeros((N_CHUNKS, n))
        self.pos = np.zeros((N_CHUNKS, n))

    def _add(self, fp, local, h, weight=None):
        for j, c in enumerate(_chunks(h)):
            fp[j] += np.bincount(local, weights=c if weight is None
                                 else c * weight, minlength=self.n)

    def add_tokens(self, tokens: np.ndarray, first_doc: int) -> None:
        """Reference side: one fed batch, doc ids from ``first_doc``."""
        r, c = np.nonzero(tokens > 0)
        t = tokens[r, c]
        local = r + (first_doc - self.lo)
        doc = local + self.lo
        self.seen[first_doc - self.lo:first_doc - self.lo + tokens.shape[0]] = True
        self.dl += np.bincount(local, minlength=self.n)
        self._add(self.post, local, _posting_hash(t, doc))
        self._add(self.pos, local, _position_hash(t, doc, c))

    def add_segment(self, seg) -> None:
        """Program side: the live docs of one recovered segment, whose
        doc ids must lie in the range."""
        ids = np.asarray(seg.doc_ids, np.int64)
        if seg.deletes is not None:
            ids = ids[~np.asarray(seg.deletes)]
        self.seen[ids - self.lo] = True
        self.dl[np.asarray(seg.doc_ids, np.int64) - self.lo] += np.asarray(
            seg.doc_len)
        ts = np.asarray(seg.term_start, np.int64)
        term = np.repeat(np.asarray(seg.terms, np.int64), np.diff(ts))
        doc = np.asarray(seg.docs, np.int64)
        self._add(self.post, doc - self.lo, _posting_hash(term, doc),
                  np.asarray(seg.tf, np.float64))
        which = np.repeat(np.arange(doc.size),
                          np.diff(np.asarray(seg.pos_start, np.int64)))
        self._add(self.pos, doc[which] - self.lo,
                  _position_hash(term[which], doc[which],
                                 np.asarray(seg.positions, np.int64)))

    @classmethod
    def of_segment(cls, seg) -> "Fingerprints":
        ids = np.asarray(seg.doc_ids, np.int64)
        lo = int(ids.min()) if ids.size else 0
        fp = cls(lo, int(ids.max()) - lo + 1 if ids.size else 0)
        fp.add_segment(seg)
        return fp

    @classmethod
    def of_tokens(cls, tokens: np.ndarray, first_doc: int) -> "Fingerprints":
        fp = cls(first_doc, tokens.shape[0])
        fp.add_tokens(tokens, first_doc)
        return fp

    def merge(self, part: "Fingerprints") -> int:
        """Add ``part`` in; returns how many of its documents fall outside
        this range."""
        lo = max(part.lo, self.lo)
        hi = min(part.lo + part.n, self.lo + self.n)
        a, b = slice(lo - self.lo, hi - self.lo), slice(lo - part.lo,
                                                        hi - part.lo)
        if hi > lo:
            self.seen[a] |= part.seen[b]
            self.dl[a] += part.dl[b]
            self.post[:, a] += part.post[:, b]
            self.pos[:, a] += part.pos[:, b]
        return int(part.seen.sum()) - (int(part.seen[b].sum())
                                       if hi > lo else 0)


def compare_fingerprints(ref: Fingerprints, got: Fingerprints,
                         extra: int) -> dict:
    """``docs_missing``: fed (acknowledged) docs absent from the recovered
    index; ``docs_mismatched``: recovered docs whose length, postings or
    positions differ from the reference's, plus recovered docs that were
    never fed."""
    missing = ref.seen & ~got.seen
    both = ref.seen & got.seen
    differ = both & ((ref.dl != got.dl)
                     | np.any(ref.post != got.post, axis=0)
                     | np.any(ref.pos != got.pos, axis=0))
    return {"docs_missing": int(missing.sum()),
            "docs_mismatched": int(differ.sum()) + int(extra)
            + int((got.seen & ~ref.seen).sum())}
