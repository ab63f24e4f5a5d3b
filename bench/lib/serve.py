"""Serving cells: BM25 top-k through ``QueryScheduler`` over the searcher
that ``DistributedIndexer.refresh()`` returns.

Set-up makes the collection from the seed, ingests it (in memory: the
serving cells measure the read path), refreshes, closes the writer (no
background work in the window) and warms the scheduler at the cell's own
load with queries the window never sends. The window is

- open loop (``loop: open``): arrivals fixed up front at ``rate_qps``;
  each request is timed from its intended arrival, so a stall charges
  every request queued behind it; after the window the queue drains;
- closed loop (``loop: closed``): ``outstanding`` requests in flight,
  each completion submits the next, cycling through a fixed set of
  ``topics`` queries as a batch run cycles through its topic file;
  timed from submit.

``correct`` compares a seeded sample of the served top-k lists, the
request with the most terms among them, against plain BM25 over the raw
tokens (``lib/reference.py``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from lib import traffic
from lib.corpus import Corpus
from lib.reference import TokenCollection, compare_topk

# Readings and reasons in PERF.md ("How correct is decided").
LIMITS = {"value_gap": 1e-4, "id_gap": 1e-4}
DRAIN_S = 60.0        # a request not served this long after the close failed


class Request:
    __slots__ = ("rid", "terms", "due", "launch", "done", "vals", "ids")

    def __init__(self, rid, terms, due):
        self.rid, self.terms, self.due = rid, terms, due
        self.launch = self.done = None
        self.vals = self.ids = None


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.requests = []
        self.steps = []            # (launch, end, n served) per step
        self.programs_in_window = 0
        self.traced = None         # PruneStats over the traced span

    # ------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        from repro.configs.base import EnvelopeConfig
        from repro.core.indexer import DistributedIndexer
        from repro.serving.query_scheduler import QueryScheduler
        from lib.harness import log
        t = time.perf_counter()
        icfg = EnvelopeConfig(**self.cfg["index"])
        self.corpus = Corpus(self.cfg["corpus"], self.seed)
        per = icfg.docs_per_shard
        self.batches = self.corpus.batches(0, self.cfg["n_docs"] // per, per)
        log(f"set-up: {len(self.batches)} batches made in "
            f"{time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        ix = DistributedIndexer(cfg=icfg)
        for b in self.batches:
            ix.index_batch(b)
        t_ing = time.perf_counter() - t
        t = time.perf_counter()
        searcher = ix.refresh()
        ix.close()
        jax.block_until_ready([r.index.packed_docs for r in searcher.readers])
        log(f"set-up: ingest {t_ing:.2f} s, refresh {time.perf_counter() - t:.2f}"
            f" s; {len(searcher.readers)} segments, "
            f"{sum(int(r.index.bw_docs.shape[0]) for r in searcher.readers)}"
            f" blocks")
        bm = self.cfg["bm25"]
        if (searcher.k1, searcher.b) != (bm["k1"], bm["b"]):
            raise ValueError(f"the searcher scores with k1={searcher.k1}, "
                             f"b={searcher.b}; the configuration states "
                             f"{bm}")
        self.block_bytes = _mean_block_bytes(searcher)
        self.searcher = searcher
        mix = self.mix
        self.sched = QueryScheduler(searcher=searcher, slots=mix["slots"],
                                    max_terms=mix["max_terms"], k=mix["k"])
        t = time.perf_counter()
        warm_rng = np.random.default_rng((self.seed, 2))
        if mix["loop"] == "open":
            # the program's own warmer (every pow2 batch x occupancy), then
            # the cell's own load, on queries the window never sends
            from repro.serving.steady import warm_searcher
            warm_searcher(searcher, traffic.queries(
                mix, self.corpus, 64, warm_rng, traffic.WARM),
                mix["slots"], mix["max_terms"], mix["k"])
            self._open_loop(mix["warm_seconds"], warm_rng, None, keep=False)
        else:
            self._closed_loop(mix["warm_batches"], warm_rng, None, keep=False)
        log(f"set-up: warm-up {time.perf_counter() - t:.2f} s")
        self._stats0 = self.sched.prune_stats.snapshot()
        self._sched0 = (self.sched.served, self.sched.steps)

    # ------------------------------------------------------------------
    def _trace(self, tracer, start: bool, stop: bool) -> None:
        """Start or stop the profiler at a step boundary, with the
        scheduler's pruning counters read at both ends."""
        if start and tracer.state == "idle":
            tracer.start()
            self._traced0 = self.sched.prune_stats.snapshot()
        elif stop and tracer.state == "on":
            tracer.stop()
            self.traced = self.sched.prune_stats.delta(self._traced0)

    def _step(self):
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("QueryScheduler.step"):
            done = self.sched.step()
        end = time.perf_counter()
        return t, end, done

    def _open_loop(self, seconds, rng, tracer, keep=True):
        from repro.serving.query_scheduler import QueryRequest
        import jax
        mix = self.mix
        arr = traffic.open_arrivals(mix["rate_qps"], seconds, rng)
        qs = traffic.queries(mix, self.corpus, arr.size, rng,
                             traffic.WINDOW if keep else traffic.WARM)
        reqs = [Request(i, q, a) for i, (q, a) in enumerate(zip(qs, arr))]
        steps = []
        t_trace = seconds / 3.0
        sched = self.sched
        max_wait = sched.max_wait_ms * 1e-3
        i, n = 0, len(reqs)
        t0 = time.perf_counter()
        for r in reqs:
            r.due += t0
        while True:
            now = time.perf_counter()
            while i < n and reqs[i].due <= now:
                sched.submit(QueryRequest(rid=i, terms=reqs[i].terms,
                                          k=mix["k"]), now=reqs[i].due)
                i += 1
            if tracer is not None:
                self._trace(tracer, now - t0 >= t_trace,
                            now - t0 >= t_trace + mix["trace_seconds"]
                            or (i == n and not sched.queue))
            if sched.ready(now):
                t, end, done = self._step()
                steps.append((t, end, len(done)))
                for q in done:
                    r = reqs[q.rid]
                    r.launch, r.done, r.vals, r.ids = t, q.t_done, \
                        q.scores, q.doc_ids
                continue
            if i == n and not sched.queue:
                break
            if now - t0 > seconds + DRAIN_S:
                break
            nxt = reqs[i].due if i < n else now + max_wait
            if sched.queue:
                nxt = min(nxt, sched.queue[0].t_submit + max_wait)
            wait = nxt - time.perf_counter()
            if wait > 0:
                with jax.profiler.TraceAnnotation("bench.wait_for_arrival"):
                    time.sleep(wait)
        if tracer is not None:
            self._trace(tracer, False, True)
        if keep:
            self.requests, self.steps = reqs, steps
            self.t0, self.t_close = t0, t0 + seconds
            self.window_s = seconds

    def _closed_loop(self, n_steps_or_seconds, rng, tracer, keep=True):
        from repro.serving.query_scheduler import QueryRequest
        mix = self.mix
        timed = keep
        pool = traffic.queries(mix, self.corpus, mix["topics"], rng,
                               traffic.WINDOW if keep else traffic.WARM)
        reqs, steps = [], []
        sched = self.sched

        def submit(now):
            # the pool's queries in turn, again and again (no result
            # cache here, so a repeat is served anew)
            r = Request(len(reqs), pool[len(reqs) % len(pool)], now)
            reqs.append(r)
            sched.submit(QueryRequest(rid=r.rid, terms=r.terms, k=mix["k"]),
                         now=now)

        t0 = time.perf_counter()
        for _ in range(mix["outstanding"]):
            submit(t0)
        t_trace = (n_steps_or_seconds / 3.0) if timed else None
        closing = False
        while sched.queue:
            now = time.perf_counter()
            if tracer is not None:
                self._trace(tracer, now - t0 >= t_trace,
                            now - t0 >= t_trace + mix["trace_seconds"]
                            or closing)
            t, end, done = self._step()
            steps.append((t, end, len(done), closing))
            for q in done:
                r = reqs[q.rid]
                r.launch, r.done, r.vals, r.ids = t, q.t_done, q.scores, \
                    q.doc_ids
            over = (end - t0 >= n_steps_or_seconds) if timed \
                else len(steps) >= n_steps_or_seconds
            closing = closing or over
            if not closing:
                for _ in done:
                    submit(time.perf_counter())
        if tracer is not None:
            self._trace(tracer, False, True)
        if keep:
            self.requests, self.steps = reqs, steps
            self.t0 = t0
            # the window closes with the last step launched before
            # ``seconds``: its rate covers whole steps only
            last = max(s[1] for s in steps if not s[3])
            self.t_close = last
            self.window_s = last - t0

    def window(self, seconds: float, tracer) -> None:
        from lib.harness import log
        rng = np.random.default_rng((self.seed, 1))
        if self.mix["loop"] == "open":
            self._open_loop(seconds, rng, tracer)
        else:
            self._closed_loop(seconds, rng, tracer)
        log(f"window: {_step_line(self.steps)}")

    # ------------------------------------------------------------------
    def end_to_end(self) -> dict:
        lat = [(r.done - r.due) * 1e3 if r.done is not None else np.inf
               for r in self.requests]
        in_window = sum(1 for r in self.requests
                        if r.done is not None and r.done <= self.t_close)
        return {"query_p95_ms": float(np.percentile(lat, 95)),
                "queries_per_s": in_window / self.window_s}

    def layer_context(self, summary, tracer):
        from lib.readers import Context
        st = self.sched.prune_stats.delta(self._stats0)
        served = self.sched.served - self._sched0[0]
        n_steps = self.sched.steps - self._sched0[1]
        tr = self.traced if summary is not None else None
        return Context(
            window_s=self.window_s, trace=summary,
            counters={"served": served, "steps": n_steps,
                      "slots": self.mix["slots"],
                      "batches": st.batches,
                      "segments_visited": st.segments_visited,
                      "blocks_candidate": st.blocks_candidate,
                      "blocks_survived": st.blocks_survived,
                      "blocks_scored": st.blocks_scored,
                      "programs_in_window": self.programs_in_window,
                      "traced_batches": tr.batches if tr else 0,
                      "traced_blocks_survived":
                          tr.blocks_survived if tr else 0},
            queue_ms=[(r.launch - r.due) * 1e3 for r in self.requests
                      if r.launch is not None],
            block_bytes=self.block_bytes)

    # ------------------------------------------------------------------
    def check(self):
        """Seeded sample of served requests (the longest always in it)
        against plain BM25 over the raw tokens."""
        done = [r for r in self.requests if r.done is not None]
        failed = len(self.requests) - len(done)
        self.sched = self.searcher = None
        gc.collect()
        sample = [done[j] for j in check_sample(
            [r.terms for r in done], self.seed, self.mix["check_sample"])]
        t = time.perf_counter()
        coll = TokenCollection(self.batches)
        vocab = (1 << int(self.cfg["corpus"]["vocab_bits"])) - 1
        post = coll.postings(np.concatenate([r.terms for r in sample])
                             if sample else [], vocab)
        bm = self.cfg["bm25"]
        gaps = [compare_topk(r.vals, r.ids,
                             *coll.bm25_scores(r.terms, post, bm["k1"],
                                               bm["b"]), self.mix["k"])
                for r in sample]
        from lib.harness import log
        log(f"check: {len(sample)} served top-{self.mix['k']} lists against "
            f"the reference in {time.perf_counter() - t:.2f} s")
        checks = {"value_gap": (max((g[0] for g in gaps), default=0.0),
                                LIMITS["value_gap"]),
                  "id_gap": (max((g[1] for g in gaps), default=0.0),
                             LIMITS["id_gap"])}
        return checks, len(self.requests), failed if sample else max(failed, 1)

    def close(self) -> None:
        self.sched = self.searcher = None


def check_sample(queries: list, seed: int, n: int) -> list:
    """Indices of the queries a run checks: the one with the most terms,
    then others in an order drawn from the seed, ``n`` in all."""
    if not queries:
        return []
    longest = max(range(len(queries)), key=lambda j: len(queries[j]))
    rest = np.random.default_rng((seed, 3)).permutation(len(queries))
    return [longest] + [int(j) for j in rest if j != longest][:n - 1]


def _step_line(steps) -> str:
    """Steps, their batch sizes and durations, for the run's log."""
    if not steps:
        return "no step"
    d = np.array([s[1] - s[0] for s in steps]) * 1e3
    n = np.array([s[2] for s in steps])
    sizes = {int(a): int(b) for a, b in zip(*np.unique(n, return_counts=True))}
    return (f"{len(steps)} steps, requests per step {sizes}; step ms p50 "
            f"{np.percentile(d, 50):.1f} p95 {np.percentile(d, 95):.1f} max "
            f"{d.max():.1f}")


def _mean_block_bytes(searcher) -> float:
    """Mean compressed bytes of one posting block over the index: the
    doc-delta and tf bit planes (128 lanes x bit width, each) plus the
    per-block metadata the scorer reads (first doc, last doc, max tf,
    shortest doc length: 4 bytes each; two bit widths: 1 byte each)."""
    total_bits, nb = 0, 0
    for r in searcher.readers:
        bwd = np.asarray(r.index.bw_docs, np.int64)
        bwt = np.asarray(r.index.bw_tf, np.int64)
        total_bits += int((bwd + bwt).sum()) * 128
        nb += bwd.size
    return (total_bits / 8 + nb * (4 * 4 + 2)) / max(nb, 1)
