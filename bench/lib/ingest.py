"""Ingest cells: bulk indexing into a durable index on local disk.

Set-up makes the window's batches from the seed ahead of time (RAM is
the source), enough for ``ahead_docs_per_s`` over the window plus one
commit group, compiles the inversion for the one flush shape the window
uses, and runs ``warm_groups`` commit groups of other documents through
an indexer of their own into a directory it then deletes, so the flush,
codec, commit and file-system paths have run once before the window
opens. The window feeds ``batch_docs``-doc batches through
``DistributedIndexer.index_batch`` into an ``FSDirectory`` and commits
after every ``commit_every_batches`` batches, so every flush has the same
shape. At ``--seconds`` the group in progress completes and its commit
closes the window: every doc fed is then durable, and the rate is docs
fed over window start -> that commit's return.

``correct`` recovers the committed index with ``open_latest`` and
compares every document's length, postings and positions with a plain
inversion of the fed tokens (``lib/reference.py``).
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

from lib.corpus import Corpus
from lib.reference import Fingerprints, compare_fingerprints

LIMITS = {"docs_missing": 0, "docs_mismatched": 0}   # exact comparison
CHECK_THREADS = 8


class Driver:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.seconds = float(seconds)
        self.programs_in_window = 0
        self.dir = None
        self.fed = 0

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.configs.base import EnvelopeConfig
        from repro.core.indexer import DistributedIndexer
        from repro.core.invert import invert_shard
        from repro.storage import FSDirectory
        from lib.harness import log
        mix = self.mix
        self.icfg = icfg = EnvelopeConfig(**self.cfg["index"])
        per, group = mix["batch_docs"], mix["commit_every_batches"]
        if per != icfg.docs_per_shard:
            raise ValueError("batch_docs differs from the index's batch")
        t = time.perf_counter()
        corpus = Corpus(self.cfg["corpus"], self.seed)
        docs = mix["ahead_docs_per_s"] * (self.seconds + mix["group_s"])
        n_groups = max(2, -(-int(docs) // (per * group)))
        self.batches = corpus.batches(0, n_groups * group, per)
        self.group = group
        log(f"set-up: {len(self.batches)} batches ({n_groups} commit groups) "
            f"made in {time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        shape = (per * group, icfg.doc_len)
        jax.block_until_ready(jax.jit(invert_shard)(
            jnp.zeros(shape, jnp.int32), 0))
        log(f"set-up: inversion of {shape} ready in "
            f"{time.perf_counter() - t:.2f} s")
        t = time.perf_counter()
        for w in range(mix["warm_groups"]):
            warm_dir = tempfile.mkdtemp(prefix="bench_ingest_warm_")
            ix = DistributedIndexer(cfg=icfg, target_dir=FSDirectory(warm_dir))
            for b in corpus.batches((n_groups + w) * group, group, per):
                ix.index_batch(b)
            ix.commit()
            ix.close()
            shutil.rmtree(warm_dir, ignore_errors=True)
        log(f"set-up: {mix['warm_groups']} warm-up commit group(s) in "
            f"{time.perf_counter() - t:.2f} s")
        self.dir = tempfile.mkdtemp(prefix="bench_ingest_")
        self.target = FSDirectory(self.dir)
        self.ix = DistributedIndexer(cfg=icfg, target_dir=self.target)

    def window(self, seconds: float, tracer) -> None:
        import jax
        ix, group = self.ix, self.group
        n_groups = len(self.batches) // group
        bytes0 = self.target.bytes_written
        self.commit_s, self.traced_docs = [], 0
        t0 = time.perf_counter()
        g = 0
        while g == 0 or time.perf_counter() - t0 < seconds:
            if g == n_groups:
                raise RuntimeError(
                    f"the window used all {n_groups} commit groups made in "
                    f"set-up; raise ahead_docs_per_s")
            if g == 1:
                tracer.start()
            for b in self.batches[g * group:(g + 1) * group]:
                with jax.profiler.TraceAnnotation(
                        "DistributedIndexer.index_batch"):
                    ix.index_batch(b)
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("DistributedIndexer.commit"):
                ix.commit()
            self.commit_s.append(time.perf_counter() - t)
            if tracer.state == "on":
                self.traced_docs += group * self.mix["batch_docs"]
                tracer.stop()
            g += 1
        self.window_s = time.perf_counter() - t0
        self.fed = g * group
        self.docs = self.fed * self.mix["batch_docs"]
        self.tokens = int(sum(int((b > 0).sum())
                              for b in self.batches[:self.fed]))
        self.bytes_written = self.target.bytes_written - bytes0

    def end_to_end(self) -> dict:
        return {"ingest_docs_per_s": self.docs / self.window_s}

    def layer_context(self, summary, tracer):
        from lib.readers import Context
        return Context(
            window_s=self.window_s, trace=summary,
            counters={"bytes_written": self.bytes_written,
                      "tokens": self.tokens,
                      "traced_docs": self.traced_docs if summary else 0,
                      "programs_in_window": self.programs_in_window},
            commit_s=self.commit_s)

    def check(self):
        """Every fed doc against a plain inversion of its tokens, after
        recovery from the committed files alone (reference and recovered
        side fingerprinted on a few threads, batch by batch and segment
        by segment)."""
        from repro.storage import FSDirectory, open_latest
        from lib.harness import log
        self.ix.close()
        self.ix = None
        gc.collect()
        t = time.perf_counter()
        per = self.mix["batch_docs"]
        ref, got = Fingerprints(0, self.docs), Fingerprints(0, self.docs)
        with ThreadPoolExecutor(CHECK_THREADS) as ex:
            segs = ex.submit(open_latest, FSDirectory(self.dir))
            for part in ex.map(Fingerprints.of_tokens, self.batches[:self.fed],
                               range(0, self.docs, per)):
                ref.merge(part)
            segs = segs.result()[1]
            extra = sum(got.merge(part) for part in
                        ex.map(Fingerprints.of_segment, segs))
        res = compare_fingerprints(ref, got, extra)
        log(f"check: {self.docs} docs in {len(segs)} recovered segments "
            f"against the reference inversion in "
            f"{time.perf_counter() - t:.2f} s")
        checks = {k: (v, LIMITS[k]) for k, v in res.items()}
        return checks, self.docs, res["docs_missing"]

    def close(self) -> None:
        if self.ix is not None:
            self.ix.close()
            self.ix = None
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None
