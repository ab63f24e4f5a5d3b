"""Benchmark harness — one function per paper table/figure + kernel
micro-benches. Prints ``name,value,derived`` CSV rows and (with ``--json``)
writes them as a ``BENCH_*.json`` artifact so CI accumulates the perf
trajectory.

  table1_envelope   the paper's Table 1: calibrated envelope vs actuals
  indexing_pipeline our own pipeline's measured throughput + alpha
  pack_kernel       lane-blocked PFor pack/unpack micro-bench
  bm25_query        block-max BM25 serving latency + pruning rate
  invert_kernel     device inversion sort throughput
  build_reader      vectorized vs scalar-loop block-index build speedup
  search_batched    batched multi-segment search qps vs batch size
  searcher_refresh  NRT refresh latency vs live segment count (cold/warm)
  merge_throughput  streaming O(P) merge vs the lexsort oracle
  index_gb_per_min  end-to-end ingest: sync vs concurrent merge scheduler
                    (flush stalls while a merge is in flight)
  envelope_measured measured media envelope: spool -> throttled index ->
                    commit -> recover -> search per source x target pair,
                    measured GB/min vs the analytic prediction
  update_heavy      document-lifecycle workload: ingest GB/min and batched
                    search latency under 10% and 50% churn (tombstoned
                    deletes + re-adds), plus merge-time compaction ratio
  search_pruned     survivor-proportional serving: compacted pruned path
                    vs exhaustive at k in {10, 100} under 10%/50% churn —
                    batched latency + candidate/survived/scored blocks,
                    plus blocks_scored on a BP-reordered vs natural merge
  compression       codec frontier: bytes/doc + encode/decode MB/s per
                    codec (raw/pfor/adaptive/pef) over one merged
                    segment, and block-max pruning on a BP-reordered vs
                    natural-order index of a clustered corpus
  fault_matrix      robustness cost: ingest GB/min + p99 search latency
                    at 0%/1%/5% injected transient-fault rates on the
                    nas profile (retried to zero giveups), cold reopen
                    through the still-faulting directory (read-path
                    retry tax), plus degraded-mode QPS with one
                    segment quarantined
  fleet             replicated sharded serving: per-replica replication
                    lag + bytes shipped, fleet and per-replica QPS over
                    scatter-gather top-k (asserted bit-identical to the
                    union oracle), and the failover cycle timed — scrub
                    detect -> quarantine/shed -> peer re-fetch -> healthy
  serve_steady      steady-state serving: open-loop Poisson driver at a
                    fixed QPS under ~10% ingest/delete churn — tail
                    latency (p50/p99/p999) for wait-for-full vs
                    continuous batching (SLO gate: continuous p99 must
                    win), generation-keyed result-cache + postings-cache
                    hit rates, and admission control past saturation
                    (typed rejections, admitted p99 vs the unbounded
                    queue's, zero wrong answers)

``--smoke`` runs a fast subset at reduced sizes (CI); ``--only NAME``
runs a single bench.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

ROWS: list[dict] = []


def emit(name: str, value: float, derived: str = "", fmt: str = ".0f"):
    ROWS.append({"name": name, "value": float(value), "derived": derived})
    print(f"{name},{value:{fmt}},{derived}")


def _time(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6, out


def table1_envelope(smoke=False):
    from repro.core.envelope import calibrate
    media, p, table = calibrate()
    errs = [abs(v["err"]) for v in table.values()]
    emit("table1_envelope.alpha", p.alpha, "merge-amplification", ".3f")
    emit("table1_envelope.c_idx", p.c_idx, "core-s-per-GB")
    emit("table1_envelope.mean_abs_err", np.mean(errs) * 100, "percent",
         ".1f")
    emit("table1_envelope.max_abs_err", np.max(errs) * 100, "percent", ".1f")
    for (s, t, col), v in sorted(table.items()):
        emit(f"table1.{s}->{t}.{col}", v["pred"],
             f"actual={v['actual']}s err={v['err']*100:+.1f}% "
             f"bound={v['bound']}")


def indexing_pipeline(smoke=False):
    from repro.configs.registry import get_arch
    from repro.core.indexer import DistributedIndexer
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus

    cfg = get_arch("lucene-envelope").smoke
    corpus = SyntheticCorpus(CW09B_SMALL, doc_buffer_len=cfg.doc_len)
    ix = DistributedIndexer(cfg=cfg, source="ceph", target="ssd")
    t0 = time.time()
    n_batches, per = (4, 64) if smoke else (8, 128)
    for i in range(n_batches):
        ix.index_batch(corpus.batch(i, per))
    ix.finalize()
    wall = time.time() - t0
    rep = ix.envelope_report()
    docs = n_batches * per
    emit("indexing.host_docs_per_s", docs / wall, "wall-clock(1-core)")
    emit("indexing.alpha_measured", rep["alpha_measured"],
         "vs-calibrated-2.74", ".2f")
    emit("indexing.modeled_gb_per_min", rep["gb_per_min_modeled"],
         f"bound={rep['bound']}", ".2f")
    emit("indexing.merge_wall_s", rep["merge_wall_s"],
         f"modeled={rep['t_merge_modeled_s']:.3f}s "
         f"n_merges={rep['n_merges']}", ".3f")


def pack_kernel(smoke=False):
    from repro.kernels.postings_pack import ref
    rng = np.random.default_rng(0)
    nb = 512 if smoke else 4096
    d = jnp.asarray(rng.integers(0, 10000, (nb, 128)).astype(np.uint32))
    pack = jax.jit(ref.pack_ref)
    us, (p, bw) = _time(pack, d)
    n_ints = nb * 128
    emit("pack_kernel.pack", us, f"{n_ints/us:.0f}Mints/s "
         f"ratio={float(ref.packed_bytes(bw))/(n_ints*4):.3f}")
    unpack = jax.jit(ref.unpack_ref)
    us2, u = _time(unpack, p, bw)
    emit("pack_kernel.unpack", us2, f"{n_ints/us2:.0f}Mints/s")
    assert (np.asarray(u) == np.asarray(d)).all()


def bm25_query(smoke=False):
    from repro.core.invert import invert_shard
    from repro.core.query import bm25_exhaustive, bm25_topk_dense
    from repro.core.searcher import IndexSearcher, SegmentReader
    from repro.core.segments import segment_from_run
    rng = np.random.default_rng(1)
    D, L, V = 2048, 64, 400
    tokens = (rng.zipf(1.25, size=(D, L)) % V + 1).astype(np.int32)
    run = invert_shard(jnp.asarray(tokens), 0)
    seg = segment_from_run({k: np.asarray(getattr(run, k))
                            for k in run._fields},
                           np.arange(D), np.asarray(run.doc_len))
    reader = SegmentReader.open(seg)
    idx = reader.index
    q = jnp.asarray(rng.choice(np.unique(tokens), 4, replace=False),
                    jnp.int32)
    f_ex = jax.jit(lambda qq: bm25_exhaustive(idx, qq, 10)[0])
    f_pr = jax.jit(lambda qq: bm25_topk_dense(idx, qq, 10)[0])
    us_ex, _ = _time(f_ex, q)
    us_pr, _ = _time(f_pr, q)
    # the compacted pruned path, through the searcher (which caches the
    # jitted metadata pass + compacted scorer — the real serving shape)
    searcher = IndexSearcher(readers=[reader])
    qn = np.asarray(q)
    us_cp, _ = _time(lambda qq: searcher.search(qq, 10)[0], qn)
    ps = searcher.prune_stats
    frac = ps.blocks_scored / max(ps.blocks_candidate, 1)
    emit("bm25.exhaustive", us_ex, f"docs={D}")
    emit("bm25.blockmax_dense", us_pr, "masked-two-phase oracle")
    emit("bm25.blockmax_compacted", us_cp, f"scored_frac={frac:.2f}")


def invert_kernel(smoke=False):
    from repro.core.invert import invert_shard
    rng = np.random.default_rng(2)
    D, L = 512, 512
    tokens = jnp.asarray(rng.integers(0, 1 << 18, (D, L)).astype(np.int32))
    f = jax.jit(lambda t: invert_shard(t, 0))
    us, _ = _time(f, tokens)
    emit("invert.sort_invert", us, f"{D*L/us:.1f}Mtok/s(1-core-cpu)")


def _cw09b_segment(n_docs=2048, doc_len=384, batch=0, base=0):
    """A CW09B_SMALL-distributed segment for read-path benches."""
    from repro.core.invert import invert_shard
    from repro.core.segments import segment_from_run
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus
    corpus = SyntheticCorpus(CW09B_SMALL, doc_buffer_len=doc_len)
    tokens = corpus.batch(batch, n_docs)
    run = invert_shard(jnp.asarray(tokens), base)
    return segment_from_run({k: np.asarray(getattr(run, k))
                             for k in run._fields},
                            np.arange(base, base + n_docs),
                            np.asarray(run.doc_len))


def build_reader(smoke=False):
    from repro.core.searcher import build_block_index, build_block_index_loop
    seg = _cw09b_segment()
    jax.block_until_ready(build_block_index(seg).packed_docs)  # warm pack

    def best_of(fn, n=2):
        best, out = float("inf"), None
        for _ in range(n):
            t0 = time.time()
            out = fn(seg)
            jax.block_until_ready(out.packed_docs)
            best = min(best, time.time() - t0)
        return best, out

    t_vec, idx_v = best_of(build_block_index)
    t_loop, idx_l = best_of(build_block_index_loop)
    same = all(np.array_equal(np.asarray(getattr(idx_v, f)),
                              np.asarray(getattr(idx_l, f)))
               for f in ("terms", "term_block_start", "idf",
                         "packed_docs", "bw_docs", "packed_tf", "bw_tf",
                         "first_doc", "max_tf", "doc_norm"))
    emit("build_reader.vectorized", t_vec * 1e6,
         f"terms={seg.n_terms} postings={seg.n_postings}")
    emit("build_reader.loop", t_loop * 1e6,
         f"speedup={t_loop/t_vec:.1f}x bit_identical={same}")


def search_batched(smoke=False):
    from repro.core.searcher import ReaderCache
    from repro.core.merge import MergeDriver
    drv = MergeDriver(fanout=10)
    for i in range(4):  # disjoint doc-id ranges, as the indexer guarantees
        drv.add_flush(_cw09b_segment(n_docs=512, doc_len=384,
                                     batch=i, base=i * 512))
    searcher = ReaderCache().refresh(drv.live_segments())
    rng = np.random.default_rng(3)
    vocab = np.unique(np.concatenate([s.terms for s in drv.live_segments()]))
    qps1 = None
    for B in (1, 8, 32):
        q = np.full((B, 4), -1, np.int32)
        for r in range(B):
            q[r] = rng.choice(vocab, 4, replace=False)
        us, _ = _time(lambda qq: searcher.search_batched(qq, 10), q)
        qps = B / (us / 1e6)
        qps1 = qps1 or qps
        emit(f"search_batched.b{B}", us,
             f"{qps:.0f}qps speedup_vs_b1={qps/qps1:.1f}x")


def searcher_refresh(smoke=False):
    from repro.core.merge import MergeDriver
    from repro.core.searcher import ReaderCache
    for n_segs in (1, 4, 16):
        drv = MergeDriver(fanout=32)  # no cascade: exactly n_segs live
        for i in range(n_segs):
            drv.add_flush(_cw09b_segment(n_docs=256, doc_len=384,
                                         batch=i, base=i * 256))
        cache = ReaderCache()
        t0 = time.time()
        cache.refresh(drv.live_segments())
        cold = time.time() - t0
        t0 = time.time()
        cache.refresh(drv.live_segments())  # all readers cached
        warm = time.time() - t0
        emit(f"searcher_refresh.segs{n_segs}", cold * 1e6,
             f"warm={warm*1e6:.0f}us builds={cache.builds} "
             f"hits={cache.hits}")


def merge_throughput(smoke=False):
    """Streaming O(P) merge vs the lexsort oracle (same inputs, identical
    output asserted). The acceptance bar is >= 3x on the merge row."""
    from repro.core.merge import merge_segments, merge_segments_sorted
    k, n_docs = (4, 512) if smoke else (10, 4096)  # k = driver fanout
    segs = [_cw09b_segment(n_docs=n_docs, doc_len=384, batch=i,
                           base=i * n_docs) for i in range(k)]
    P = sum(s.n_postings for s in segs)

    def best_of(fn, n=3):
        best, out = float("inf"), None
        for _ in range(n):
            t0 = time.perf_counter()
            out = fn(segs)
            best = min(best, time.perf_counter() - t0)
        return best, out

    t_new, m_new = best_of(merge_segments)
    t_old, m_old = best_of(merge_segments_sorted)
    same = all(np.array_equal(getattr(m_new, f), getattr(m_old, f))
               for f in ("terms", "term_start", "docs", "tf", "positions",
                         "pos_start", "doc_ids", "doc_len"))
    assert same, "streaming merge diverged from the lexsort oracle"
    emit("merge.streaming", t_new * 1e6,
         f"segs={k} postings={P} {P/t_new/1e6:.1f}Mpost/s")
    emit("merge.lexsort", t_old * 1e6,
         f"speedup={t_old/t_new:.1f}x bit_identical={same}")


def index_gb_per_min(smoke=False):
    """End-to-end ingest at media speed: the same batch stream through the
    synchronous write path (merges stall flushes) and the concurrent
    scheduler (merges ride background threads). The stall row is the max
    ``index_batch`` wall time — in sync mode the cascade-triggering batch
    pays the whole merge; with the scheduler it must not."""
    import dataclasses
    from repro.configs.registry import get_arch
    from repro.core.indexer import DistributedIndexer
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus

    cfg = get_arch("lucene-envelope").smoke
    n_batches, per, doc_len = (8, 128, 128) if smoke else (16, 512, 256)
    cfg = dataclasses.replace(cfg, doc_len=doc_len)
    corpus = SyntheticCorpus(CW09B_SMALL, doc_buffer_len=doc_len)
    batches = [corpus.batch(i, per) for i in range(n_batches)]
    results = {}
    for threads in (0, 2):
        ix = DistributedIndexer(cfg=cfg, source="ceph", target="ssd",
                                merge_threads=threads)
        lat = []
        t0 = time.perf_counter()
        for b in batches:
            t1 = time.perf_counter()
            ix.index_batch(b)
            lat.append(time.perf_counter() - t1)
        ingest_wall = time.perf_counter() - t0
        # cascade merges only: finalize()'s force merge is inline by design
        if ix.merge_scheduler is not None:
            ix.merge_scheduler.drain()  # land in-flight cascades first
        cascade_merge_wall = ix.merger.merge_wall_s
        ix.finalize()
        total_wall = time.perf_counter() - t0
        gb = ix.stats.read_bytes / 1e9
        results[threads] = {
            "gb_per_min": gb / (total_wall / 60),
            "ingest_gb_per_min": gb / (ingest_wall / 60),
            "max_flush_ms": max(lat) * 1e3,
            "merge_wall_s": cascade_merge_wall,
            "n_merges": ix.merger.n_merges,
        }
        ix.close()
    sync, conc = results[0], results[2]
    emit("index_gb_per_min.sync", sync["gb_per_min"],
         f"ingest={sync['ingest_gb_per_min']:.2f} "
         f"n_merges={sync['n_merges']}", ".2f")
    emit("index_gb_per_min.concurrent", conc["gb_per_min"],
         f"ingest={conc['ingest_gb_per_min']:.2f} "
         f"speedup={conc['gb_per_min']/sync['gb_per_min']:.2f}x "
         f"merge_wall={conc['merge_wall_s']:.2f}s(backgrounded)", ".2f")
    emit("index_gb_per_min.flush_stall_sync_ms", sync["max_flush_ms"],
         "max index_batch wall (pays merge inline)", ".1f")
    emit("index_gb_per_min.flush_stall_concurrent_ms", conc["max_flush_ms"],
         f"stall_free={conc['max_flush_ms'] <= sync['max_flush_ms']}", ".1f")


def envelope_measured(smoke=False):
    """The paper's experiment, measured instead of modeled: spool the
    corpus into a throttled source Directory, index it through a throttled
    target FSDirectory (tempdir), commit, then recover from the committed
    bytes and search. Device time comes from each DeviceThrottle's exact
    timeline (same pair on one throttle = shared controller), so measured
    GB/min is deterministic; the analytic ``core/envelope.py`` prediction
    for the emulated Table-1 pair prints alongside. Smoke runs the two
    acceptance pairs (isolated nas->ssd vs shared ssd->ssd); the full run
    sweeps all nine source x target combinations."""
    import shutil
    import tempfile

    from repro.configs.registry import get_arch
    from repro.core import envelope as env
    from repro.core.indexer import DistributedIndexer
    from repro.core.searcher import ReaderCache
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus, spool_corpus
    from repro.storage import (DeviceThrottle, FSDirectory, MEDIA_PROFILES,
                               RAMDirectory, ThrottledDirectory, open_latest)

    cfg = get_arch("lucene-envelope").smoke
    n_batches, per = (6, 64) if smoke else (10, 128)
    corpus = SyntheticCorpus(CW09B_SMALL, doc_buffer_len=cfg.doc_len)
    profiles = ("nas", "disk", "ssd")
    pairs = [("nas", "ssd"), ("ssd", "ssd")] if smoke else \
        [(s, t) for s in profiles for t in profiles]
    reports = {}
    root = tempfile.mkdtemp(prefix="envelope_measured_")
    try:
        for sp, tp in pairs:
            # same profile name = the same physical device here: one
            # throttle timeline serves both streams (shared controller)
            th_t = DeviceThrottle(MEDIA_PROFILES[tp])
            th_s = th_t if sp == tp else DeviceThrottle(MEDIA_PROFILES[sp])
            src = ThrottledDirectory(RAMDirectory(), th_s)
            tgt_path = f"{root}/{sp}__{tp}"
            tgt = ThrottledDirectory(FSDirectory(tgt_path), th_t)
            spool_corpus(corpus, src, n_batches, per)
            src.reset_counters()
            th_s.reset()  # spooling predates the run (th_t untouched yet)
            ix = DistributedIndexer(cfg=cfg,
                                    source=env.PROFILE_TO_MEDIA[sp],
                                    target=env.PROFILE_TO_MEDIA[tp],
                                    source_dir=src, target_dir=tgt)
            ix.index_spooled()
            ix.finalize()
            rep = ix.envelope_report()
            # recover from the committed bytes and prove them servable
            gen, segs = open_latest(FSDirectory(tgt_path))
            searcher = ReaderCache().refresh(segs)
            assert searcher.n_docs == n_batches * per, \
                (searcher.n_docs, n_batches * per)
            q = np.asarray(segs[0].terms[:3], np.int32)
            v, ids = searcher.search(q, 5)
            assert int(np.asarray(ids)[0]) >= 0
            reports[(sp, tp)] = rep
            last_segs = segs
            emit(f"envelope_measured.{sp}->{tp}",
                 rep["gb_per_min_measured"],
                 f"modeled={rep['gb_per_min_modeled']:.2f}GB/min "
                 f"enc={rep['index_bytes_encoded']/1e3:.0f}KB "
                 f"raw={rep['index_bytes_raw']/1e3:.0f}KB "
                 f"shared={rep['shared_media_measured']} "
                 f"recovered_gen={gen}", ".3f")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    iso, sh = reports[("nas", "ssd")], reports[("ssd", "ssd")]
    speedup = iso["gb_per_min_measured"] / sh["gb_per_min_measured"]
    assert speedup > 1.0, "isolated media must beat the shared pair"
    emit("envelope_measured.isolation_speedup", speedup,
         "isolated nas->ssd vs shared ssd->ssd (paper's headline result)",
         ".2f")
    # refit the analytic model against this repo's own measured runs
    mruns = [env.measured_run_from_report(s, t, r, "t_io_measured_s")
             for (s, t), r in reports.items()]
    _, p, _ = env.calibrate(measured=mruns, measured_weight=0.1)
    emit("envelope_measured.alpha_recalibrated", p.alpha,
         f"calibrate() incl. {len(mruns)} measured runs", ".3f")
    # bytes-on-media per codec for the committed doc set just recovered:
    # at least one of the new codecs must land strictly below the
    # bit-plane (pfor) baseline
    from repro.storage import codec as sc
    enc = {c: sum(sum(len(b) for b in sc.encode_segment(s, c).values())
                  for s in last_segs) for c in sc.CODECS}
    for c in sc.CODECS:
        emit(f"envelope_measured.codec_bytes.{c}", enc[c],
             f"ratio_vs_pfor={enc[c]/enc['pfor']:.3f}")
    assert min(enc["adaptive"], enc["pef"]) < enc["pfor"], \
        (f"no codec beat the bit-plane baseline: {enc}")


def update_heavy(smoke=False):
    """Document lifecycle under churn: a base corpus is ingested, then
    10% / 50% of its docs are replaced (tombstone + re-add — the
    update-heavy regime where Asadi & Lin's incremental indexes earn
    their keep). Rows: churn-phase ingest GB/min (tombstones are cheap
    bitmaps, so this should stay near the append-only rate), batched
    search latency over the live (masked) snapshot, and the live doc
    count after finalize — which also proves merge-time compaction
    returned the index to exactly the corpus size."""
    from repro.configs.registry import get_arch
    from repro.core.indexer import DistributedIndexer
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus

    cfg = get_arch("lucene-envelope").smoke
    n_base, per = (6, 64) if smoke else (12, 256)
    corpus = SyntheticCorpus(CW09B_SMALL, doc_buffer_len=cfg.doc_len)
    n_docs = n_base * per
    for churn in (0.10, 0.50):
        ix = DistributedIndexer(cfg=cfg, merge_threads=2)
        for i in range(n_base):
            ix.index_batch(corpus.batch(i, per))
        base_read = ix.stats.read_bytes
        n_upd = int(churn * n_docs)
        t0 = time.perf_counter()
        done = 0
        while done < n_upd:
            m = min(per, n_upd - done)
            # replace docs [done, done+m): bulk tombstone + one re-add
            # batch (the fresh docs get new ids; corpus size is steady)
            ix.delete(np.arange(done, done + m))
            ix.index_batch(corpus.batch(n_base + done // per, m))
            done += m
        searcher = ix.refresh()
        churn_wall = time.perf_counter() - t0
        assert searcher.n_docs == n_docs, (searcher.n_docs, n_docs)
        gb = (ix.stats.read_bytes - base_read) / 1e9
        tag = f"churn{int(churn * 100)}"
        emit(f"update_heavy.{tag}.ingest_gb_per_min",
             gb / (churn_wall / 60),
             f"replaced {n_upd}/{n_docs} docs in {churn_wall*1000:.0f}ms",
             ".3f")
        rep = ix.envelope_report()
        vocab = np.unique(corpus.batch(0, 64))[1:]
        rng = np.random.default_rng(5)
        q = np.stack([rng.choice(vocab, 4, replace=False) for _ in range(8)]
                     ).astype(np.int32)
        us, _ = _time(lambda qq: searcher.search_batched(qq, 10), q)
        emit(f"update_heavy.{tag}.search_ms_b8", us / 1e3,
             f"live={rep['live_docs']} tombstoned={rep['deleted_docs']}",
             ".2f")
        final = ix.finalize()
        assert final.n_docs == n_docs and not final.has_deletes
        emit(f"update_heavy.{tag}.compacted_docs", final.n_docs,
             f"deletes_acked={rep['deletes_acked']} "
             f"n_merges={ix.merger.n_merges}")
        ix.close()


def search_pruned(smoke=False):
    """Survivor-proportional serving vs exhaustive, under churn: ingest a
    base corpus, replace 10% / 50% of its docs (tombstone + re-add), then
    serve the same batched queries through the compacted pruned path
    (phase-1 probe -> host MaxScore -> bucket-padded survivor scoring,
    with cross-segment theta sharing) and the dense exhaustive baseline.
    Rows per (churn, k): batched latency for both paths plus the
    PruneStats counters (candidate vs survived vs scored blocks). The
    acceptance bar: blocks_scored strictly below blocks_candidate, pruned
    top-k bit-identical to exhaustive, and pruned batched latency at or
    below exhaustive at k=10 on CPU."""
    import dataclasses
    from repro.configs.registry import get_arch
    from repro.core.indexer import DistributedIndexer
    from repro.core.searcher import IndexSearcher
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus

    cfg = get_arch("lucene-envelope").smoke
    # short docs keep tf off BM25's saturation plateau and many docs push
    # theta's quantile out — the regime where block bounds actually bite;
    # a real flush budget yields few LARGE segments (heavy terms span
    # dozens of blocks each), which is what serving tiers look like
    n_base, per, doc_len = (8, 2048, 64) if smoke else (16, 2048, 64)
    cfg = dataclasses.replace(cfg, doc_len=doc_len, flush_budget_mb=4)
    corpus = SyntheticCorpus(CW09B_SMALL, doc_buffer_len=doc_len)
    n_docs = n_base * per
    rng = np.random.default_rng(7)

    def best_of(fn, n=5):
        best, out = float("inf"), None
        for _ in range(2):
            jax.block_until_ready(fn())  # warm compiles
        for _ in range(n):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best * 1e6, out

    for churn in (0.10, 0.50):
        ix = DistributedIndexer(cfg=cfg, merge_threads=2)
        for i in range(n_base):
            ix.index_batch(corpus.batch(i, per))
        n_upd = int(churn * n_docs)
        done = 0
        while done < n_upd:
            m = min(per, n_upd - done)
            ix.delete(np.arange(done, done + m))
            ix.index_batch(corpus.batch(n_base + done // per, m))
            done += m
        pruned = ix.refresh()
        exhaustive = IndexSearcher(readers=pruned.readers, k1=pruned.k1,
                                   b=pruned.b, prune=False)
        # the web-search query shape: short, dominated by one frequent
        # term whose postings span many blocks (that is where skipping
        # pays — term-level MaxScore bounds cannot eliminate blocks of
        # balanced multi-term disjunctions on an iid corpus); two queries
        # add a mid-frequency term to keep the multi-term path honest
        tok = corpus.batch(0, 512)
        vals, counts = np.unique(tok[tok > 0], return_counts=True)
        order = np.argsort(-counts)
        heavy = vals[order[:16]]
        mid = vals[order[len(order) // 8:len(order) // 4]]
        B = 8
        q = np.full((B, 2), -1, np.int32)
        q[:, 0] = rng.choice(heavy, B, replace=False)
        q[B - 2:, 1] = rng.choice(mid, 2, replace=False)
        tag = f"churn{int(churn * 100)}"
        for k in (10, 100):
            us_pr, (v_pr, i_pr) = best_of(
                lambda: pruned.search_batched(q, k))
            us_ex, (v_ex, i_ex) = best_of(
                lambda: exhaustive.search_batched(q, k))
            assert np.array_equal(np.asarray(v_pr), np.asarray(v_ex)), \
                f"pruned top-k diverged from exhaustive ({tag}, k={k})"
            mark = pruned.prune_stats.snapshot()
            pruned.search_batched(q, k)
            st = pruned.prune_stats.delta(mark)
            emit(f"search_pruned.{tag}.k{k}.pruned_ms", us_pr / 1e3,
                 f"exhaustive={us_ex/1e3:.2f}ms "
                 f"speedup={us_ex/us_pr:.2f}x "
                 f"segs_skipped={st.segments_skipped}", ".2f")
            emit(f"search_pruned.{tag}.k{k}.blocks", st.blocks_scored,
                 f"candidate={st.blocks_candidate} "
                 f"survived={st.blocks_survived} "
                 f"skip_rate={st.skip_rate:.2f}")
            if k == 10:
                assert st.blocks_scored < st.blocks_candidate, \
                    (f"pruning must beat exhaustive block counts "
                     f"({st.blocks_scored} >= {st.blocks_candidate})")
                assert us_pr <= us_ex, \
                    (f"pruned batched latency must not exceed exhaustive "
                     f"at k=10 ({us_pr:.0f}us > {us_ex:.0f}us)")
        ix.close()
    # same serving path, one more lever: BP doc-id reassignment at merge
    # time cuts blocks_scored at equal k and bit-identical scores
    _bp_reorder_contrast("search_pruned", smoke)
    # and the tentpole A/B: BMW doc-range-overlap bounds vs term-level
    # MaxScore on a balanced-disjunction workload
    _bmw_contrast(smoke)


def _bmw_contrast(smoke=False):
    """True block-max WAND vs term-level MaxScore, A/B on the SAME
    balanced-disjunction workload over a segment whose terms live in
    (mostly) private doc ranges — the clustered regime a BP-reordered
    crawl converges to. Balanced multi-term disjunctions of comparable
    weight are exactly where the term-level "others" sum cannot
    eliminate anything (every term's best block is assumed to help
    everywhere) while the doc-range-overlap bound drops cross-term help
    between blocks whose doc extents never meet. Gates: top-k
    bit-identical (values AND ids), blocks_scored strictly lower under
    BMW. Emits the ``search_pruned.bmw.*`` A/B rows."""
    from repro.core.searcher import ReaderCache
    from repro.core.segments import Segment

    rng = np.random.default_rng(11)
    n_big, n_small, span = 16, 8, (2000 if smoke else 4000)
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
    seg = Segment(terms=np.arange(n_terms, dtype=np.int64),
                  term_start=np.asarray(term_start, np.int64),
                  docs=np.asarray(docs, np.int64), tf=tf,
                  positions=np.concatenate([np.arange(c) for c in tf]),
                  pos_start=pos_start,
                  doc_ids=np.arange(N, dtype=np.int64), doc_len=doc_len)
    # balanced disjunctions: 3 heavy terms + 1 single-block term each
    B = 8
    q = np.stack([np.concatenate([rng.choice(n_big, 3, replace=False),
                                  [n_big + rng.integers(0, n_small)]])
                  for _ in range(B)]).astype(np.int32)

    def serve(bmw, midgrid):
        s = ReaderCache(bmw=bmw, midgrid=midgrid).refresh([seg])
        v, i = s.search_batched(q, 10)
        return np.asarray(v), np.asarray(i), s.prune_stats

    v_b, i_b, st_b = serve(True, True)
    v_m, i_m, st_m = serve(False, False)
    assert np.array_equal(v_b, v_m) and np.array_equal(i_b, i_m), \
        "BMW top-k diverged from the MaxScore baseline"
    assert st_b.blocks_scored < st_m.blocks_scored, \
        (f"BMW must score strictly fewer blocks than MaxScore "
         f"({st_b.blocks_scored} >= {st_m.blocks_scored})")
    cut = 1.0 - st_b.blocks_scored / st_m.blocks_scored
    assert cut >= 0.30, \
        f"BMW block cut fell below the 30% envelope target ({cut:.2f})"
    emit("search_pruned.bmw.blocks_scored", st_b.blocks_scored,
         f"maxscore={st_m.blocks_scored} cut={cut:.2f} "
         f"survived={st_b.blocks_survived}/{st_m.blocks_survived} "
         f"terms_eliminated={st_b.terms_eliminated} "
         f"midgrid_skipped={st_b.blocks_skipped_midgrid} "
         f"bit_identical=True")


def _bp_reorder_contrast(prefix, smoke=False):
    """Merge-time doc-id reassignment (BP) on a clustered corpus, served.
    Topic-mixture corpus (the clustered regime real crawls sit in):
    natural order interleaves topics so every block holds one short
    high-impact doc and block upper bounds saturate; BP groups each
    topic's docs, making blocks impact-homogeneous and skippable. Emits
    ``{prefix}.reorder.*`` rows; asserts bit-identical scores and a
    strict blocks_scored cut at equal k."""
    import dataclasses
    from repro.core.invert import invert_shard
    from repro.core.merge import merge_segments
    from repro.core.searcher import ReaderCache
    from repro.core.segments import segment_from_run
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus

    spec = dataclasses.replace(CW09B_SMALL, n_topics=8, doc_len_sigma=0.3)
    per, nb, dl = (1024 if smoke else 2048), 8, 128
    corpus = SyntheticCorpus(spec, doc_buffer_len=dl)
    segs = []
    for i in range(nb):
        run = invert_shard(jnp.asarray(corpus.batch(i, per)), i * per)
        segs.append(segment_from_run(
            {k: np.asarray(getattr(run, k)) for k in run._fields},
            np.arange(i * per, (i + 1) * per), np.asarray(run.doc_len)))
    m_nat = merge_segments(segs)
    t0 = time.perf_counter()
    m_re = merge_segments(segs, reorder=True)
    t_bp = time.perf_counter() - t0
    # heavy single-term queries whose postings span many blocks — the
    # query shape where block skipping (and hence doc order) matters
    tok = corpus.batch(0, 1024)
    vals, counts = np.unique(tok[tok > 0], return_counts=True)
    heavy = vals[np.argsort(-counts)[:16]]
    rng = np.random.default_rng(7)
    B = 8
    q = np.full((B, 2), -1, np.int32)
    q[:, 0] = rng.choice(heavy, B, replace=False)

    def serve(seg):
        s = ReaderCache(prune=True).refresh([seg])
        v, _ = s.search_batched(q, 10)
        return np.asarray(v), s.prune_stats

    v_nat, st_nat = serve(m_nat)
    v_re, st_re = serve(m_re)
    assert np.array_equal(v_nat, v_re), \
        "BP-reordered scores diverged from the natural-order index"
    assert st_re.blocks_scored < st_nat.blocks_scored, \
        (f"reordering must cut scored blocks "
         f"({st_re.blocks_scored} >= {st_nat.blocks_scored})")
    emit(f"{prefix}.reorder.blocks_scored_natural", st_nat.blocks_scored,
         f"candidate={st_nat.blocks_candidate} "
         f"survived={st_nat.blocks_survived}")
    emit(f"{prefix}.reorder.blocks_scored_bp", st_re.blocks_scored,
         f"survived={st_re.blocks_survived} "
         f"scored_ratio={st_re.blocks_scored/st_nat.blocks_scored:.2f} "
         f"bp_wall_s={t_bp:.1f} postings={m_nat.n_postings} "
         f"bit_identical=True")


def compression(smoke=False):
    """The compression frontier, measured: every registered codec
    encodes + decodes one merged CW09B-shaped segment (bytes/doc, MB/s,
    bit-identical round-trip asserted), with the doc-id-gap stream
    broken out — partitioned Elias-Fano must beat the raw baseline
    there. Then merge-time doc-id reassignment (BP) on a clustered
    (topic-mixture) corpus: the same batched queries served off the
    natural-order and the BP-reordered merge must return bit-identical
    scores while the reordered index scores strictly fewer blocks."""
    import dataclasses
    from repro.core.invert import invert_shard
    from repro.core.merge import merge_segments
    from repro.core.searcher import ReaderCache
    from repro.core.segments import segment_from_run
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus
    from repro.storage import codec as sc

    # --- per-codec bytes on media + encode/decode throughput ---------
    seg = _cw09b_segment(n_docs=1024 if smoke else 2048, doc_len=128)
    stream_bytes = 8.0 * (2 * seg.n_terms + 2 * seg.n_postings
                          + len(seg.positions) + 2 * seg.n_docs)
    df = np.diff(seg.term_start).astype(np.int64)
    doc_delta = sc._rebase_encode(seg.docs, seg.term_start[:-1], df)
    sizes, doc_bytes = {}, {}
    for codec in sc.CODECS:
        t0 = time.perf_counter()
        files = sc.encode_segment(seg, codec)
        t_enc = time.perf_counter() - t0
        t0 = time.perf_counter()
        dec = sc.decode_segment(files)
        t_dec = time.perf_counter() - t0
        for f in ("terms", "term_start", "docs", "tf", "positions",
                  "pos_start", "doc_ids", "doc_len"):
            assert np.array_equal(getattr(dec, f), getattr(seg, f)), \
                f"codec {codec!r} round-trip diverged on {f}"
        sizes[codec] = sum(len(b) for b in files.values())
        doc_bytes[codec] = len(sc._enc_stream(doc_delta, codec))
        emit(f"compression.{codec}.bytes_per_doc",
             sizes[codec] / seg.n_docs,
             f"docid_gap_bytes={doc_bytes[codec]} "
             f"enc={stream_bytes/t_enc/1e6:.0f}MB/s "
             f"dec={stream_bytes/t_dec/1e6:.0f}MB/s", ".1f")
    assert doc_bytes["pef"] < doc_bytes["raw"], \
        (f"PEF doc-id gaps must beat the raw baseline "
         f"({doc_bytes['pef']} >= {doc_bytes['raw']})")
    emit("compression.pef_docid_ratio_vs_raw",
         doc_bytes["pef"] / doc_bytes["raw"],
         f"pfor={doc_bytes['pfor']/doc_bytes['raw']:.3f} "
         f"adaptive={doc_bytes['adaptive']/doc_bytes['raw']:.3f}", ".3f")

    # --- PEF stream throughput (the vectorized chunk decode) ---------
    # isolated on the doc-id-gap stream so codec dispatch / segment
    # framing don't dilute the number; best-of-3 wall clocks
    enc_pef = sc._enc_stream(doc_delta, "pef")
    mb = doc_delta.size * 8 / 1e6

    def _clock(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    t_enc = min(_clock(lambda: sc._enc_stream(doc_delta, "pef"))
                for _ in range(3))
    t_dec = min(_clock(lambda: sc._dec_stream(enc_pef, 0))
                for _ in range(3))
    out, _ = sc._dec_stream(enc_pef, 0)
    assert np.array_equal(out, doc_delta), \
        "pef stream decode diverged from its input"
    emit("compression.pef.stream_dec_mb_s", mb / t_dec,
         f"enc={mb/t_enc:.0f}MB/s n={doc_delta.size} "
         f"bytes={len(enc_pef)}", ".0f")

    # --- BP doc-id reassignment on a clustered corpus ----------------
    _bp_reorder_contrast("compression", smoke)


def fault_matrix(smoke=False):
    """Robustness cost, measured: the same ingest -> commit -> search
    cycle on the throttled nas profile with seeded transient faults
    injected at 0%/1%/5% of directory ops. The RetryPolicy-wrapped
    target must heal every one (giveups stay zero, every acked doc is
    searchable), and the rows price what that healing costs in ingest
    GB/min and p99 batched-search latency. Then degraded-mode serving:
    one committed segment bit-rotted and quarantined, the scheduler
    keeps taking traffic against the survivors and reports QPS plus the
    missing-doc count."""
    import dataclasses
    from repro.configs.registry import get_arch
    from repro.core.indexer import DistributedIndexer
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus
    from repro.serving.query_scheduler import QueryRequest, QueryScheduler
    from repro.storage import (DeviceThrottle, FaultInjectingDirectory,
                               MEDIA_PROFILES, RAMDirectory,
                               RetryingDirectory, RetryPolicy,
                               ThrottledDirectory, open_searcher)

    cfg = get_arch("lucene-envelope").smoke
    n_batches, per, doc_len = (6, 64, 128) if smoke else (12, 256, 192)
    cfg = dataclasses.replace(cfg, doc_len=doc_len)
    corpus = SyntheticCorpus(CW09B_SMALL, doc_buffer_len=doc_len)
    batches = [corpus.batch(i, per) for i in range(n_batches)]
    # heavy terms -> queries whose postings actually span blocks
    tok = batches[0]
    vals, counts = np.unique(tok[tok > 0], return_counts=True)
    heavy = vals[np.argsort(-counts)[:32]].astype(np.int32)
    rng = np.random.default_rng(23)
    B = 8
    q = np.full((B, 4), -1, np.int32)
    q[:, :2] = rng.choice(heavy, (B, 2))
    n_search = 12 if smoke else 40

    # --- ingest + serve under 0% / 1% / 5% transient-fault rates -----
    # compile outside the matrix: the storage flush path (codec pack
    # kernels) is shape-jitted, so the warm-up must write through a
    # target_dir or the 0%-rate row pays the whole compile
    warm = DistributedIndexer(cfg=cfg, target_dir=RAMDirectory())
    for b in batches:
        warm.index_batch(b)
    warm.commit()
    warm.close()
    for rate in (0.0, 0.01, 0.05):
        fi = FaultInjectingDirectory(
            ThrottledDirectory(RAMDirectory(),
                               DeviceThrottle(MEDIA_PROFILES["nas"])),
            seed=17, p_transient=rate, p_torn=rate / 5, transient_repeat=2)
        # cap must cover stacked gates (sync = list + sync): see
        # storage/retry.py — 2 * transient_repeat, plus headroom
        ix = DistributedIndexer(
            cfg=cfg, target_dir=fi,
            retry_policy=RetryPolicy(max_retries=6, base_delay_s=1e-4,
                                     max_delay_s=2e-3, seed=17))
        t0 = time.perf_counter()
        for b in batches:
            ix.index_batch(b)
        ix.commit()
        wall = time.perf_counter() - t0
        gb = ix.stats.read_bytes / 1e9
        searcher = ix.refresh()
        assert searcher.n_docs == n_batches * per, \
            (f"acked docs lost under fault rate {rate}: "
             f"{searcher.n_docs} != {n_batches * per}")
        searcher.search_batched(q, 10)     # compile outside the timer
        lat = []
        for _ in range(n_search):
            t1 = time.perf_counter()
            searcher.search_batched(q, 10)
            lat.append(time.perf_counter() - t1)
        rep = ix.envelope_report()
        assert rep["io_giveups"] == 0, \
            f"retry cap breached at fault rate {rate}"
        ix.close()
        tag = f"fault_matrix.t{rate * 100:g}"
        emit(f"{tag}.ingest_gb_per_min", gb / (wall / 60),
             f"faults_injected={fi.injected['transient'] + fi.injected['torn']} "
             f"io_retries={rep['io_retries']} giveups=0", ".3f")
        emit(f"{tag}.search_p99_ms",
             float(np.percentile(lat, 99)) * 1e3,
             f"batch={B} n={n_search} (in-memory snapshot post-recovery)",
             ".2f")
        # the READ side of the same tax: re-open the committed index
        # through the still-faulting directory (a cold restart on flaky
        # media) — every segment decode replays the retry gauntlet
        rdir = RetryingDirectory(fi, RetryPolicy(max_retries=6,
                                                 base_delay_s=1e-4,
                                                 max_delay_s=2e-3, seed=29))
        t0 = time.perf_counter()
        _, reopened = open_searcher(rdir)
        t_open = time.perf_counter() - t0
        assert reopened.n_docs == n_batches * per, \
            (f"reopen through faults lost docs at rate {rate}: "
             f"{reopened.n_docs} != {n_batches * per}")
        assert rdir.giveups == 0, f"reopen giveups at fault rate {rate}"
        reopened.search_batched(q, 10)
        t1 = time.perf_counter()
        reopened.search_batched(q, 10)
        emit(f"{tag}.reopen_ms", t_open * 1e3,
             f"io_retries={rdir.retries} giveups=0 "
             f"warm_search_ms={(time.perf_counter()-t1)*1e3:.2f}", ".1f")

    # --- degraded serving: one committed segment quarantined ---------
    fi = FaultInjectingDirectory(RAMDirectory(), seed=3)  # disarmed
    ix = DistributedIndexer(cfg=cfg, target_dir=fi, merge_threads=0)
    for b in batches:
        ix.index_batch(b)
        ix.commit()                        # one commit point per batch
    names = sorted(ix.store._names.values())
    ix.close()

    def qps_of(searcher):
        sched = QueryScheduler(searcher=searcher, slots=B, max_terms=4,
                               k=10)
        sched.submit(QueryRequest(rid=-1, terms=heavy[:2]))
        sched.step()                       # compile outside the timer
        n_req = 64 if smoke else 256
        for i in range(n_req):
            sched.submit(QueryRequest(rid=i, terms=rng.choice(heavy, 2)))
        t0 = time.perf_counter()
        done = sched.run_to_completion()
        return len(done) / (time.perf_counter() - t0), sched

    _, healthy = open_searcher(fi)
    qps_full, _ = qps_of(healthy)
    fi.corrupt_file(names[0] + ".dict")    # post-commit bit rot
    _, degraded = open_searcher(fi, degraded=True)
    qps_deg, sched = qps_of(degraded)
    assert sched.degraded and sched.missing_docs > 0, \
        "degraded snapshot must carry its casualty count"
    emit("fault_matrix.degraded_qps", qps_deg,
         f"healthy_qps={qps_full:.0f} quarantined=1 "
         f"missing_docs={sched.missing_docs} served={sched.served}", ".0f")


def fleet(smoke=False):
    """Replicated, sharded serving fleet: two shard writers publish
    commits, two replicas per shard pull them (manifest shipping), and a
    ``FleetSearcher`` scatter-gathers global top-k. Rows: replication
    lag + bytes shipped per replica, fleet and per-replica QPS, and the
    failover cycle timed end to end — bit rot detected, traffic shed to
    the peer (zero failed queries, asserted against the single-index
    union oracle), segment re-fetched, replica healthy again."""
    from repro.configs.registry import get_arch
    from repro.core.indexer import DistributedIndexer
    from repro.core.searcher import ReaderCache
    from repro.data.corpus import CW09B_SMALL, SyntheticCorpus
    from repro.replication import (CommitPublisher, FleetSearcher,
                                   ReplicaSyncer)
    from repro.storage import RAMDirectory, open_latest

    cfg = get_arch("lucene-envelope").smoke
    n_shards, n_rep, base = 2, 2, 1_000_000
    n_batches, per = (3, 64) if smoke else (6, 128)
    corpus = SyntheticCorpus(CW09B_SMALL, doc_buffer_len=cfg.doc_len)

    writers, pubs = [], []
    for si in range(n_shards):
        d = RAMDirectory()
        pub = CommitPublisher(d)
        ix = DistributedIndexer(cfg=cfg, target_dir=d, publisher=pub,
                                doc_base=si * base)
        for i in range(n_batches):
            ix.index_batch(corpus.batch(8 * si + i, per))
        ix.delete(np.arange(si * base + 3, si * base + 9))
        ix.commit()
        writers.append(ix)
        pubs.append(pub)

    shards = []
    for si in range(n_shards):
        group = []
        for ri in range(n_rep):
            r = ReplicaSyncer(RAMDirectory(), writers[si].target_dir,
                              replica_id=f"s{si}r{ri}", publisher=pubs[si])
            t0 = time.perf_counter()
            out = r.sync_once()
            t_sync = time.perf_counter() - t0
            emit(f"fleet.replication.s{si}r{ri}.lag_s", out["lag_s"],
                 f"sync_wall_ms={t_sync*1e3:.1f} files={out['files']} "
                 f"bytes={out['bytes']}", ".4f")
            group.append(r)
        for r in group:
            r.peers = [p.directory for p in group if p is not r]
        shards.append(group)
    emit("fleet.replication.bytes_shipped_total",
         sum(p.report()["bytes_shipped_total"] for p in pubs),
         f"replicas={n_shards * n_rep} max_lag_s="
         f"{max(p.report()['max_replication_lag_s'] for p in pubs):.4f}")

    fleet_s = FleetSearcher(shards)
    union_segs = []
    for ix in writers:
        union_segs.extend(open_latest(ix.target_dir)[1])
    oracle = ReaderCache(prune=False).refresh(union_segs)

    tok = corpus.batch(0, 256)
    vals, counts = np.unique(tok[tok > 0], return_counts=True)
    heavy = vals[np.argsort(-counts)[:32]].astype(np.int32)
    rng = np.random.default_rng(31)
    B = 8
    q = rng.choice(heavy, (B, 3)).astype(np.int32)
    fv, _ = fleet_s.search_batched(q, 10)      # warm compiles + stats
    ov, _ = oracle.search_batched(q, 10)
    assert np.array_equal(np.asarray(fv), np.asarray(ov)), \
        "fleet top-k diverged from the union oracle"
    n_q = 24 if smoke else 96
    t0 = time.perf_counter()
    for i in range(n_q):
        fleet_s.search_batched(rng.choice(heavy, (B, 3)).astype(np.int32),
                               10)
    wall = time.perf_counter() - t0
    rep = fleet_s.report()
    emit("fleet.qps", n_q * B / wall,
         f"shards={n_shards} replicas={n_shards * n_rep} batch={B} "
         f"shards_skipped={rep['shards_skipped']}", ".0f")
    for rid in sorted(rep["served"]):
        emit(f"fleet.qps.{rid}", rep["served"][rid] * B / wall,
             f"batches_served={rep['served'][rid]}", ".0f")

    # failover cycle, timed: rot -> quarantine (shed) -> re-fetch -> healthy
    bad = shards[0][0]
    victim = next(n for n in bad.directory.list_files()
                  if n.endswith(".pst"))
    data = bytearray(bad.directory.read_file(victim))
    data[len(data) // 2] ^= 0xFF
    bad.directory.write_file(victim, bytes(data))
    from repro.storage import ChecksumScrubber
    t0 = time.perf_counter()
    found = ChecksumScrubber(bad.directory).sweep()   # anti-entropy scan
    assert victim in found, found
    base = bad.quarantine(victim)               # shed traffic to the peer
    t_detect_s = time.perf_counter() - t0
    failed, mark = 0, fleet_s.report()["failovers"]
    for i in range(4):                          # the degraded window
        qq = rng.choice(heavy, (B, 3)).astype(np.int32)
        fv, _ = fleet_s.search_batched(qq, 10)
        ov, _ = oracle.search_batched(qq, 10)
        failed += int(not np.array_equal(np.asarray(fv), np.asarray(ov)))
    shed = fleet_s.report()["failovers"] - mark
    out = bad.repair(base)                      # re-fetch from the peer
    recovery_s = time.perf_counter() - t0
    assert failed == 0, "failover served wrong results"
    assert bad.healthy and out["files"] >= 1 and shed >= 1
    emit("fleet.failover_recovery_ms", recovery_s * 1e3,
         f"detect_ms={t_detect_s*1e3:.1f} refetched_files={out['files']} "
         f"refetch_bytes={out['bytes']} shed_batches={shed} "
         f"failed_queries=0", ".1f")
    for ix in writers:
        ix.close()


def serve_steady(smoke=False):
    """Steady-state serving, measured open-loop: a seeded Poisson
    arrival stream at a fixed target QPS drives the scheduler while the
    standard ~10% churn loop (index + delete + refresh + swap) mutates
    the index underneath. Three measured contrasts, each SLO-gated:

    1. Batching policy A/B at the same offered load: wait-for-full
       (``full_batch=True``, the old policy) puts the inter-arrival gap
       of a whole batch into every tail; continuous batching launches
       partials after ``max_wait_ms``. Gate: continuous p99 < full p99,
       nothing shed, nothing lost.
    2. Generation-keyed result cache + hot-term postings cache hit
       rates under churn (every refresh swap bumps the generation, so
       hits are bit-identical by construction — asserted in the test
       suite, priced here).
    3. Admission control past saturation: service time pinned with a
       sleep floor so overload is deterministic, then the same offered
       storm with and without ``admit_cap``. Gate: the bounded queue's
       admitted p99 beats the unbounded queue's, rejections are typed
       and counted, and every admitted answer is bit-identical to the
       direct-searcher oracle (zero wrong answers)."""
    import dataclasses
    from repro.configs.registry import get_arch
    from repro.core.indexer import DistributedIndexer
    from repro.serving.query_scheduler import QueryRequest, QueryScheduler
    from repro.serving.steady import (ResultCache, make_churn,
                                      run_open_loop, warm_searcher)
    from repro.storage import RAMDirectory, open_latest

    # merge_fanout raised so no merge fires inside the short measured
    # windows: a churn-triggered merge is a multi-second compile storm
    # that buries the policy contrast for BOTH arms — the merge-under-
    # serve tax is priced in index_gb_per_min / update_heavy
    cfg = dataclasses.replace(get_arch("lucene-envelope").smoke,
                              postings_cache_mb=4.0, merge_fanout=64)
    rng = np.random.default_rng(41)
    n_docs, qps, duration = (256, 75, 0.8) if smoke else (1024, 75, 2.0)
    ix = DistributedIndexer(cfg=cfg, target_dir=RAMDirectory(),
                            merge_threads=0)
    toks = rng.integers(1, 4096, (n_docs, cfg.doc_len)).astype(np.int32)
    ix.index_batch(toks)
    ix.commit()
    searcher = ix.refresh()

    vals, counts = np.unique(toks[toks > 0], return_counts=True)
    heavy = vals[np.argsort(-counts)[:32]].astype(np.int32)
    pool = [rng.choice(heavy, 3).astype(np.int32) for _ in range(8)]
    slots, max_terms, k = 8, 4, 10
    warm_searcher(searcher, pool, slots, max_terms, k)
    searcher.search(pool[0], k)            # the oracle path, warmed too
    # throwaway churn ticks warm the write path's compile shapes (flush
    # pack kernels; tick 4 deletes, compiling the masked evaluators of
    # the seed segments) before anything is measured
    pre = QueryScheduler(searcher=searcher, slots=slots,
                         max_terms=max_terms, k=k)
    tick = make_churn(ix, pre, rng, warm_pool=pool)
    for _ in range(5):
        tick()

    # ~10% churn = doc-ops as a fraction of queries served. Churn is
    # bounded by TICK COUNT, not wall time: each tick flushes a new
    # segment whose evaluators must compile, so unbounded interval-
    # driven ticks are a positive feedback loop (slow ticks stretch the
    # wall, the wall admits more ticks) that starves both arms alike.
    n_ticks = 3 if smoke else 7

    def drive(full_batch, cache=None, tag=None):
        s = ix.refresh()                   # never serve a cold snapshot:
        warm_searcher(s, pool, slots, max_terms, k)  # the warmer is
        sched = QueryScheduler(searcher=s, slots=slots,  # the swap contract
                               max_terms=max_terms, k=k,
                               full_batch=full_batch, max_wait_ms=2.0,
                               cache=cache)
        churn = make_churn(ix, sched, rng, docs_per_tick=2,
                           delete_every=2, warm_pool=pool)
        left = [n_ticks]

        def bounded_churn():
            if left[0] > 0:
                left[0] -= 1
                churn()

        rep = run_open_loop(sched, pool, qps=qps, duration_s=duration,
                            seed=43, churn=bounded_churn,
                            churn_interval_s=duration / (n_ticks + 1))
        assert rep.completed == rep.offered and rep.rejected == 0, \
            f"{tag}: lost admitted traffic ({rep.row()})"
        return rep, sched

    # --- batching policy A/B at the same offered load ----------------
    full, _ = drive(full_batch=True, tag="full_batch")
    cont, csched = drive(full_batch=False, tag="continuous")
    assert cont.p99_ms < full.p99_ms, \
        (f"continuous batching must beat wait-for-full at {qps} QPS: "
         f"p99 {cont.p99_ms:.2f}ms >= {full.p99_ms:.2f}ms")
    assert cont.qps_achieved >= 0.5 * qps, \
        f"driver failed to sustain load: {cont.qps_achieved:.0f}/{qps}"
    emit("serve_steady.full_batch.p99_ms", full.p99_ms,
         f"p50={full.p50_ms:.2f} p999={full.p999_ms:.2f} "
         f"qps={full.qps_achieved:.0f} offered={full.offered}", ".2f")
    emit("serve_steady.continuous.p99_ms", cont.p99_ms,
         f"p50={cont.p50_ms:.2f} p999={cont.p999_ms:.2f} "
         f"partial_steps={csched.partial_steps}/{csched.steps} "
         f"mean_depth={cont.mean_queue_depth:.1f}", ".2f")
    emit("serve_steady.continuous.qps_achieved", cont.qps_achieved,
         f"target={qps} churn=10% wall_s={cont.wall_s:.2f}", ".0f")

    # --- cache hit rates under churn ---------------------------------
    cache = ResultCache(cap_bytes=1 << 20)
    cached, _ = drive(full_batch=False, cache=cache, tag="cached")
    crep = cache.report()
    erep = ix.envelope_report()
    emit("serve_steady.result_cache.hit_rate",
         crep["hits"] / max(1, crep["hits"] + crep["misses"]),
         f"hits={crep['hits']} misses={crep['misses']} "
         f"entries={crep['entries']} bytes={crep['bytes']} "
         f"served_cached={cached.cached}", ".3f")
    # NRT serving never touches the directory (segments are handed from
    # the writer in memory), so the postings cache is priced on its real
    # workload: a cold reopen of the committed index fills it, a second
    # reopen (restart / replica refresh) reads through it
    ix.commit()
    r0 = ix.envelope_report()
    open_latest(ix.target_dir)
    r1 = ix.envelope_report()
    open_latest(ix.target_dir)
    r2 = ix.envelope_report()
    warm_h = r2["postings_cache_hits"] - r1["postings_cache_hits"]
    warm_m = r2["postings_cache_misses"] - r1["postings_cache_misses"]
    emit("serve_steady.postings_cache.hit_rate",
         warm_h / max(1, warm_h + warm_m),
         f"warm_reopen_hits={warm_h} warm_reopen_misses={warm_m} "
         f"cold_fill_misses="
         f"{r1['postings_cache_misses'] - r0['postings_cache_misses']} "
         f"bytes={r2['postings_cache_bytes']}", ".3f")

    # --- admission control past saturation ---------------------------
    class _SlowSearcher:                   # pins service time: overload
        def __init__(self, inner, delay_s):     # is deterministic, not
            self._inner, self._delay_s = inner, delay_s  # machine-luck
        def __getattr__(self, name):
            return getattr(self._inner, name)
        def search_batched(self, q, kk, theta0=None):
            time.sleep(self._delay_s)
            return self._inner.search_batched(q, kk, theta0=theta0)

    storm_snap = ix.refresh()
    warm_searcher(storm_snap, pool, 4, max_terms, k)
    slow = _SlowSearcher(storm_snap, 0.004)
    storm_qps, storm_s = (2000, 0.12) if smoke else (2500, 0.2)

    def storm(admit_cap):
        sched = QueryScheduler(searcher=slow, slots=4, max_terms=max_terms,
                               k=k, max_wait_ms=2.0, admit_cap=admit_cap)
        rep = run_open_loop(sched, pool, qps=storm_qps, duration_s=storm_s,
                            seed=47)
        assert rep.completed + rep.rejected == rep.offered, rep.row()
        return rep, sched

    unshed, _ = storm(admit_cap=0)
    shed, ssched = storm(admit_cap=8)
    assert shed.rejected > 0 and shed.rejected == ssched.rejected, \
        "saturation storm must shed typed rejections"
    assert shed.p99_ms < unshed.p99_ms, \
        (f"admission control must bound admitted p99 past saturation: "
         f"{shed.p99_ms:.1f}ms >= {unshed.p99_ms:.1f}ms")
    oracle = ix.refresh()
    for req in [r for r in shed.requests if r.done][:16]:
        _, oi = oracle.search(req.terms, k)         # zero wrong answers
        np.testing.assert_array_equal(np.asarray(req.doc_ids),
                                      np.asarray(oi))
    emit("serve_steady.admission.p99_ms", shed.p99_ms,
         f"unbounded_p99={unshed.p99_ms:.1f} rejected={shed.rejected}/"
         f"{shed.offered} admit_cap=8 wrong_answers=0", ".2f")
    emit("serve_steady.admission.shed_rate",
         shed.rejected / shed.offered,
         f"offered_qps={storm_qps} completed={shed.completed}", ".3f")

    # --- open-loop ramp: locate the saturation knee -------------------
    # same pinned-service-time searcher (4 slots x 4 ms/batch plus the
    # real search -> a hard throughput ceiling), offered rate doubling
    # each step. Below the knee, achieved throughput scales with offered
    # load and p99 is set by service time; past it the open-loop queue
    # integrates, throughput plateaus, and p99 is set by the window
    # length instead. The knee is the step where achieved QPS peaks.
    ramp_steps = (150, 300, 600, 1200, 2400)
    ramp_s = 0.12 if smoke else 0.25
    sweep = []
    for target in ramp_steps:
        sched = QueryScheduler(searcher=slow, slots=4,
                               max_terms=max_terms, k=k, max_wait_ms=2.0)
        rep = run_open_loop(sched, pool, qps=target, duration_s=ramp_s,
                            seed=53)
        sweep.append((target, rep.qps_achieved, rep.p99_ms))
    ach = np.array([a for _, a, _ in sweep])
    best = int(ach.argmax())
    assert best > 0 and ach[best] > 1.5 * ach[0], \
        f"throughput never scaled with offered load: {sweep}"
    assert best < len(ramp_steps) - 1, \
        f"ramp never crossed the saturation knee: {sweep}"
    emit("serve_steady.ramp.saturation_qps", ach[best],
         f"knee_offered={ramp_steps[best]} sweep="
         + " ".join(f"{t}:{a:.0f}qps/{p:.1f}ms" for t, a, p in sweep),
         ".0f")
    ix.close()


BENCHES = [table1_envelope, indexing_pipeline, pack_kernel, bm25_query,
           invert_kernel, build_reader, search_batched, searcher_refresh,
           merge_throughput, index_gb_per_min, envelope_measured,
           update_heavy, search_pruned, compression, fault_matrix, fleet,
           serve_steady]
SMOKE_BENCHES = [table1_envelope, indexing_pipeline, pack_kernel,
                 invert_kernel, merge_throughput, index_gb_per_min]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset at reduced sizes")
    ap.add_argument("--json", metavar="PATH",
                    help="also write rows as a BENCH_*.json artifact")
    ap.add_argument("--only", metavar="NAME",
                    help="run a single bench by function name")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    benches = SMOKE_BENCHES if args.smoke else BENCHES
    if args.only:
        benches = [b for b in BENCHES if b.__name__ == args.only]
        if not benches:
            raise SystemExit(f"unknown bench {args.only!r}; one of "
                             f"{[b.__name__ for b in BENCHES]}")
    print("name,value,derived")
    t0 = time.time()
    for bench in benches:
        bench(smoke=args.smoke)
    if args.json:
        dev = jax.devices()[0]
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke,
                       "backend": jax.default_backend(),
                       "platform": dev.platform,
                       "device_kind": dev.device_kind,
                       "device_count": jax.device_count(),
                       "wall_s": time.time() - t0,
                       "benches": [b.__name__ for b in benches],
                       "rows": ROWS}, f, indent=1)
        print(f"# wrote {len(ROWS)} rows -> {args.json}")


if __name__ == "__main__":
    main()
