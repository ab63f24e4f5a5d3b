"""The one traffic generator: reads a mix file's parameters.

Every seed gets the same work in another order, so a seed changes which
term ids are asked and in what order, not how much work a run offers:

- queries: their lengths (``term_shares``, length -> share) and the
  corpus rank of each term come from the collection's fixed stream
  (``stream_seed``), so every seed asks the same queries by rank; the
  seed relabels them through the collection's rank -> term permutation
  and shuffles their order;
- open-loop arrivals: ``round(rate_qps * seconds)`` gaps at the
  quantiles of the exponential law (a Poisson process's gaps), shuffled
  by the seed, fixed before serving starts;
- ingest: the collection's batches, each from its own fixed stream.
"""
from __future__ import annotations

import numpy as np

QUERY_STREAM = 0x9E7     # the query streams' tag within ``stream_seed``
WINDOW, WARM = 0, 1      # the window's queries, the warm-up's


def query_sizes(mix: dict, n: int, rng) -> np.ndarray:
    """``n`` query lengths in the shares of ``term_shares`` (largest
    remainder), shuffled by ``rng``."""
    shares = mix["term_shares"]
    lengths = np.array(sorted(int(k) for k in shares))
    w = np.array([float(shares[str(x)]) for x in lengths])
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:rest]] += 1
    return rng.permutation(np.repeat(lengths, counts))


def open_arrivals(rate_qps: float, seconds: float, rng) -> np.ndarray:
    """Intended arrival times in ``[0, seconds)``, ascending."""
    n = max(1, int(round(rate_qps * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds * (n - 0.5) / n / gaps.sum()
    return np.cumsum(rng.permutation(gaps))


def queries(mix: dict, corpus, n: int, rng, stream: int = WINDOW) -> list:
    """``n`` queries, the same by rank for every seed, in ``rng``'s order."""
    fixed = np.random.default_rng((int(corpus.spec["stream_seed"]),
                                   QUERY_STREAM, int(stream)))
    qs = corpus.query_terms(fixed, query_sizes(mix, n, fixed),
                            int(mix["skip_top_terms"]))
    return [qs[i] for i in rng.permutation(n)]
