"""ClueWeb-shaped documents and queries, made from the seed.

The law is the one ``repro.data.corpus`` and the chip smoke draw from:
lognormal document lengths around the configuration's median, clipped to
``[min_doc_len, doc_len]``, and Zipf-Mandelbrot term ranks (exponent
``zipf_s``, offset ``zipf_q``) over ``2^vocab_bits - 1`` ids, mapped
through a rank -> term-id permutation (hashed ids are not rank-ordered).

What the seed decides: the permutation, so the term ids of every
document and every query. What the configuration's ``stream_seed``
fixes: each document's length and the rank of each of its tokens (and,
in ``lib/traffic.py``, each query's ranks). A seed therefore relabels one
collection of fixed shape: every seed's index has the same number of
terms, postings and blocks per segment, so the program compiles the same
programs for every seed (it compiles one per array shape), while the
term ids, and so the order of each segment's term dictionary, change
with the seed.

This copy fills each batch with one masked store instead of a loop over
documents, and draws batches on a few threads: batch ``i`` reads only
its own stream ``(stream_seed, i)``, so the tokens do not depend on the
thread that made them.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

GEN_THREADS = 8


class Corpus:
    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.seed = int(seed)
        self.doc_len = int(spec["doc_len"])
        vocab = (1 << int(spec["vocab_bits"])) - 1
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        w = 1.0 / np.power(ranks + float(spec["zipf_q"]), float(spec["zipf_s"]))
        self.cdf = np.cumsum(w / w.sum())
        self.cdf /= self.cdf[-1]
        rng = np.random.default_rng((self.seed, 0x5EED))
        self.rank_to_term = (rng.permutation(vocab) + 1).astype(np.int32)

    def batch(self, index: int, n_docs: int) -> np.ndarray:
        """(n_docs, doc_len) int32 term ids, 0 = padding after each doc."""
        rng = np.random.default_rng((int(self.spec["stream_seed"]),
                                     int(index)))
        L = self.doc_len
        lens = rng.lognormal(np.log(self.spec["median_doc_len"]),
                             self.spec["doc_len_sigma"], size=n_docs)
        lens = np.clip(lens.astype(np.int64), self.spec["min_doc_len"], L)
        ranks = np.searchsorted(self.cdf, rng.random(int(lens.sum())),
                                side="right")
        out = np.zeros((n_docs, L), np.int32)
        out[np.arange(L)[None, :] < lens[:, None]] = self.rank_to_term[ranks]
        return out

    def batches(self, first: int, count: int, n_docs: int) -> list:
        with ThreadPoolExecutor(GEN_THREADS) as ex:
            return list(ex.map(lambda i: self.batch(i, n_docs),
                               range(first, first + count)))

    def query_terms(self, rng, sizes, skip_top: int) -> list:
        """One query per entry of ``sizes``: that many distinct terms, each
        drawn at its corpus frequency from the ranks past the ``skip_top``
        most frequent (a stop list of that many words)."""
        lo = self.cdf[skip_top - 1] if skip_top > 0 else 0.0
        out = []
        for n in sizes:
            q = []
            while len(q) < n:
                u = lo + (1.0 - lo) * rng.random(2 * n)
                for r in np.searchsorted(self.cdf, u, side="right"):
                    t = int(self.rank_to_term[min(r, self.cdf.size - 1)])
                    if t not in q and len(q) < n:
                        q.append(t)
            out.append(np.asarray(q, np.int32))
        return out
