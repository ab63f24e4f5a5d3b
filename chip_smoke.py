#!/usr/bin/env python3
"""Chip smoke: the retrieval main path on a TPU, checked end to end.

Drives what ``python -m repro.launch.serve --mode retrieval --index-dir``
drives, through the same library calls, at the production
``lucene_envelope`` config over a ClueWeb09b-shaped synthetic corpus:

    DistributedIndexer over an FSDirectory -> index_batch -> commit
    -> open_searcher (recovery) -> QueryScheduler (32 slots, k=10,
    4-term queries) -> delete -> commit -> open_searcher -> QueryScheduler

The first recovery has no tombstones, so its segments serve through the
midgrid kernel; the second serves the tombstoned segments through the
masked skip kernel. Every served top-k is checked against a plain numpy
BM25 over the committed segments and their live-doc statistics, and no
deleted doc may come back. Before that, each Pallas kernel of the path
is checked against its jnp reference on the chip. Timings printed here
are smoke figures, not benchmarks.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # four shards, one per chip: fleet
                                        # merge over a 4-device mesh vs the
                                        # host merge and a union oracle

Exits non-zero, and prints no result line, when JAX finds no TPU. The
last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

K, SLOTS, QUERY_TERMS = 10, 32, 4
SHARD_RANGE = 1 << 24       # doc ids per shard in the four-chip fleet
RTOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# set-up: devices, compile accounting, corpus
# ---------------------------------------------------------------------------

def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found — JAX's first device is "
                         f"{devs[0].platform!r}; this smoke runs only on a "
                         f"TPU")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU chips, JAX sees {len(devs)}")
    return devs


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"{self.seconds:.2f} s backend compile over {self.compiles} "
                f"executables, {self.cache_hits} persistent-cache hits")


def clueweb_corpus(seed: int, doc_len: int):
    """ClueWeb09b-shaped stream: lognormal lengths around 384 tokens
    (sigma 0.7), Zipf-Mandelbrot terms (s=1.2, q=2.7) over 2^22 ids."""
    from repro.data.corpus import CorpusSpec, SyntheticCorpus
    spec = CorpusSpec("cw09b", n_docs=0, mean_doc_len=384,
                      doc_len_sigma=0.7, vocab_bits=22, zipf_s=1.2,
                      zipf_q=2.7, seed=seed)
    return SyntheticCorpus(spec, doc_buffer_len=doc_len)


def make_batches(corpus, first: int, n_docs: int, per_batch: int):
    return [corpus.batch(first + i, per_batch)
            for i in range(-(-n_docs // per_batch))]


def make_queries(batches, n: int, seed: int):
    """``n`` queries of 4 distinct terms, each term a random token of the
    corpus (so terms come at their corpus frequency)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    toks = batches[0][batches[0] > 0]
    out = np.empty((n, QUERY_TERMS), np.int32)
    for i in range(n):
        q = []
        while len(q) < QUERY_TERMS:
            t = int(toks[rng.integers(toks.size)])
            if t not in q:
                q.append(t)
        out[i] = q
    return out


# ---------------------------------------------------------------------------
# kernel parity on the chip
# ---------------------------------------------------------------------------

def check_kernels(seed: int) -> None:
    """Each Pallas kernel of the path against its jnp reference, on the
    chip, at small sizes (including block counts that need padding)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.bm25_blockmax import kernel as bk, ref as br
    from repro.kernels.postings_pack import kernel as pk, ref as pr
    rng = np.random.default_rng(seed)

    def same(name, got, want):
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            if not np.array_equal(g.reshape(w.shape), w):
                raise AssertionError(f"{name}: Pallas kernel differs from "
                                     f"its reference on the chip")

    nb = 1000
    d = rng.integers(0, 1 << 20, (nb, 128)).astype(np.uint32)
    d[3] = 0
    d[5] = 0xFFFFFFFF
    p_ref, bw_ref = pr.pack_ref(jnp.asarray(d))
    same("pack", pk.pack_pallas(jnp.asarray(d), interpret=False),
         (p_ref, bw_ref))
    same("unpack", [pk.unpack_pallas(p_ref, bw_ref, interpret=False)], [d])

    nb = 600
    deltas = rng.integers(0, 50, (nb, 128)).astype(np.uint32)
    deltas[:, 0] = 0
    deltas[0] = 0
    tf = rng.integers(0, 30, (nb, 128)).astype(np.uint32)
    pd, bwd = pr.pack_ref(jnp.asarray(deltas))
    pt, bwt = pr.pack_ref(jnp.asarray(tf))
    first = jnp.asarray(rng.integers(0, 5000, nb).astype(np.int32))
    idf = jnp.asarray((rng.random(nb) * 4).astype(np.float32))
    act = jnp.asarray((rng.random(nb) < 0.8).astype(np.int32))
    want = br.bm25_blocks_ref(pd, bwd, first, pt, bwt, idf, act)
    same("skip", bk.bm25_blocks_pallas(pd, bwd, first, pt, bwt, idf, act,
                                       interpret=False), want)
    wd, cd = pr.compact_words(np.asarray(pd), np.asarray(bwd))
    wt, ct = pr.compact_words(np.asarray(pt), np.asarray(bwt))
    same("compact", bk.bm25_blocks_compact_pallas(
        jnp.asarray(wd), jnp.asarray(cd), bwd, first, jnp.asarray(wt),
        jnp.asarray(ct), bwt, idf, act, interpret=False), want)
    rows = jnp.asarray(rng.integers(0, 4, nb).astype(np.int32))
    ubf = jnp.asarray((rng.random(nb) * 8).astype(np.float32))
    theta = jnp.zeros((1, 128), jnp.float32).at[0, :4].set(0.5)
    mid = (pd[:512], bwd[:512], first[:512], pt[:512], bwt[:512],
           idf[:512], act[:512], rows[:512], ubf[:512], theta,
           jnp.float32(1.3))
    same("midgrid", bk.bm25_blocks_midgrid_pallas(*mid, k=10,
                                                  interpret=False),
         br.bm25_blocks_midgrid_ref(*mid, k=10))
    log("kernels: pack, unpack, skip, compact and midgrid match their jnp "
        "references bit for bit on the chip")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def reference_scores(segs, q, k1: float, b: float):
    """Plain BM25 over committed segments, live docs only: returns
    ``(doc ids, scores)`` for every live doc matching a term of ``q``,
    ids ascending. Reads the Segment arrays directly (terms, CSR
    postings, doc lengths, tombstones) and shares no code with the
    scoring path."""
    import numpy as np
    live = [np.ones(s.n_docs, bool) if s.deletes is None else ~s.deletes
            for s in segs]
    n = sum(int(lv.sum()) for lv in live)
    avgdl = max(sum(float(s.doc_len[lv].sum())
                    for s, lv in zip(segs, live)) / n, 1.0)
    all_docs, all_scores = [np.zeros(0, np.int64)], [np.zeros(0)]
    for t in q:
        docs, tfs, dls = [], [], []
        for s, lv in zip(segs, live):
            i = int(np.searchsorted(s.terms, t))
            if i == s.n_terms or s.terms[i] != t:
                continue
            lo, hi = int(s.term_start[i]), int(s.term_start[i + 1])
            loc = np.searchsorted(s.doc_ids, s.docs[lo:hi])
            keep = lv[loc]
            docs.append(s.docs[lo:hi][keep])
            tfs.append(s.tf[lo:hi][keep])
            dls.append(s.doc_len[loc[keep]])
        if not docs:
            continue
        docs, tf, dl = (np.concatenate(a) for a in (docs, tfs, dls))
        df = docs.size
        idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
        tf = tf.astype(np.float64)
        all_docs.append(docs.astype(np.int64))
        all_scores.append(idf * (k1 + 1.0) * tf
                          / (tf + k1 * (1.0 - b + b * dl / avgdl)))
    ids, inv = np.unique(np.concatenate(all_docs), return_inverse=True)
    return ids, np.bincount(inv, weights=np.concatenate(all_scores),
                            minlength=ids.size)


def check_topk(ref, vals, ids, deleted: set, k: int) -> None:
    """Served (vals, ids) against the reference ``(doc ids, scores)``:
    values equal to the reference top-k within RTOL, every served doc
    carrying its own reference score, the docs above the k-th score
    exactly the reference's, and no deleted doc."""
    import numpy as np
    ref_ids, ref_s = ref
    ids = [int(i) for i in ids]
    vals = np.asarray(vals, np.float64)
    bad = deleted.intersection(ids)
    if bad:
        raise AssertionError(f"deleted docs served: {sorted(bad)[:5]}")
    order = np.argsort(-ref_s, kind="stable")[:k]
    want = np.concatenate([ref_s[order], np.zeros(k - order.size)])
    if not np.allclose(vals, want, rtol=RTOL, atol=0.0):
        raise AssertionError(f"top-k values differ: served {vals}, "
                             f"reference {want}")
    for d, v in zip(ids, vals):
        j = int(np.searchsorted(ref_ids, d))
        own = ref_s[j] if j < ref_ids.size and ref_ids[j] == d else 0.0
        if v > 0 and not np.isclose(own, v, rtol=RTOL, atol=0):
            raise AssertionError(f"doc {d} served at {v}, reference {own}")
    above = set(ref_ids[ref_s > want[-1] * (1 + RTOL)].tolist())
    if not above <= set(ids):
        raise AssertionError(f"docs above the k-th score missing: "
                             f"{sorted(above - set(ids))[:5]}")


# ---------------------------------------------------------------------------
# the write path: index -> delete -> commit
# ---------------------------------------------------------------------------

def ingest(cfg, batches, directory, doc_base: int):
    """Index ``batches`` into a durable writer over ``directory`` and
    commit. Returns ``(open writer, seconds)``."""
    from repro.core.indexer import DistributedIndexer
    from repro.storage import FSDirectory
    ix = DistributedIndexer(cfg=cfg, target_dir=FSDirectory(directory),
                            doc_base=doc_base)
    t0 = time.perf_counter()
    for tokens in batches:
        ix.index_batch(tokens)
    ix.commit()
    return ix, time.perf_counter() - t0


def delete_and_commit(ix, doc_base: int, n_docs: int, seed: int,
                      frac: float = 1 / 32):
    """Tombstone a seeded sample of the writer's docs, commit, close.
    Returns ``(deleted ids, seconds)``."""
    import numpy as np
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    deleted = doc_base + rng.choice(n_docs, size=int(n_docs * frac),
                                    replace=False)
    ix.delete(deleted)
    ix.commit()
    dt = time.perf_counter() - t0
    ix.close()
    return set(deleted.tolist()), dt


def index_shape(searcher) -> str:
    segs = [r.seg for r in searcher.readers]
    blocks = sum(int(r.index.bw_docs.shape[0]) for r in searcher.readers)
    return (f"{sum(s.n_docs for s in segs)} docs "
            f"({searcher.n_docs} live), "
            f"{sum(s.n_postings for s in segs)} postings, {blocks} blocks, "
            f"{len(segs)} segments")


def pallas_evidence(searcher, midgrid: bool) -> None:
    """The survivor scorer the searcher's largest segment serves through
    (the midgrid scorer for a tombstone-free one, else the masked skip
    scorer) and the pack its readers ran lower to Mosaic kernels
    (``tpu_custom_call``), not to the jnp references."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.searcher import _index_arrays
    from repro.kernels.postings_pack import ops as pack_ops
    r = max(searcher.readers, key=lambda r: r.n_docs)
    _, scorer, mid = r._pruned_fns(K, 1, SLOTS, midgrid=midgrid)
    n = 128
    args = (_index_arrays(r.index), r.doc_map, jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.float32), jnp.zeros(n, bool),
            jnp.zeros(n, jnp.int32))
    if midgrid:
        name = "midgrid survivor scorer"
        text = mid.lower(*args, jnp.zeros(n, jnp.float32),
                         jnp.zeros(SLOTS, jnp.float32),
                         jnp.ones(r.n_docs, jnp.float32)).as_text()
    else:
        name = "masked survivor scorer"
        text = scorer.lower(*args, jnp.ones(r.n_docs, jnp.float32),
                            r.live).as_text()
    pack_text = pack_ops.pack.lower(jax.ShapeDtypeStruct(
        (int(r.index.bw_docs.shape[0]), 128), np.uint32)).as_text()
    for what, t in ((name, text), ("pack", pack_text)):
        if "tpu_custom_call" not in t:
            raise AssertionError(f"the {what} did not lower to a Pallas "
                                 f"kernel")
    log(f"pallas: the {name} and the segment pack lower to "
        f"tpu_custom_call (Mosaic kernels), not to the jnp references")


def recover(root: str, what: str):
    """``open_searcher`` over the committed directory, readers built."""
    import jax
    from repro.storage import FSDirectory, open_searcher
    t0 = time.perf_counter()
    gen, searcher = open_searcher(FSDirectory(root))
    jax.block_until_ready([r.index.packed_docs for r in searcher.readers])
    log(f"recovery ({what}): commit gen {gen} opened and readers built in "
        f"{time.perf_counter() - t0:.1f} s (smoke figure); "
        f"{index_shape(searcher)}")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"device memory: bytes_in_use {mem.get('bytes_in_use')} after the "
        f"readers are built (peak {mem.get('peak_bytes_in_use')})")
    return searcher


def serve_and_check(searcher, queries, deleted: set, what: str) -> None:
    """Serve ``queries`` through a ``QueryScheduler`` (the first batch
    compiles) and check every top-k against the plain reference."""
    from repro.serving.query_scheduler import QueryRequest, QueryScheduler
    sched = QueryScheduler(searcher=searcher, slots=SLOTS,
                           max_terms=QUERY_TERMS, k=K)
    reqs = [QueryRequest(rid=i, terms=q, k=K) for i, q in enumerate(queries)]
    for r in reqs[:SLOTS]:
        sched.submit(r)
    sched.step()
    for r in reqs[SLOTS:]:
        sched.submit(r)
    t0 = time.perf_counter()
    done = reqs[:SLOTS] + sched.run_to_completion()
    dt = time.perf_counter() - t0
    rest = len(done) - SLOTS
    rate = f"{rest / dt:.0f} qps" if rest else "no later batch"
    st = searcher.prune_stats
    log(f"serving ({what}): {len(done)} queries in {len(done) // SLOTS} "
        f"batches of {SLOTS}; {rest} after the first batch in {dt:.2f} s "
        f"({rate}, smoke figure); {st.segments_visited} segment visits, "
        f"{st.blocks_scored} blocks scored, {st.blocks_skipped_midgrid} "
        f"skipped in-grid by the midgrid kernel")
    segs = [r.seg for r in searcher.readers]
    for r in done:
        check_topk(reference_scores(segs, r.terms, searcher.k1, searcher.b),
                   r.scores, r.doc_ids, deleted, K)
    log(f"oracle ({what}): all {len(done)} served top-{K} lists equal the "
        f"numpy BM25 reference over the committed segments (rtol {RTOL}); "
        f"no deleted doc served")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(args, clock) -> None:
    import gc
    from repro.configs.lucene_envelope import CONFIG as cfg

    check_kernels(args.seed)
    log(f"config: {cfg.name} (codec {cfg.codec}, {cfg.docs_per_shard} "
        f"docs per batch, {cfg.doc_len}-token buffers, vocab "
        f"2^{cfg.vocab_bits}, {cfg.flush_budget_mb} MB flush budget, "
        f"merge fanout {cfg.merge_fanout}); corpus ClueWeb09b-shaped, "
        f"{args.docs} docs, seed {args.seed}")
    if args.docs < 1 << 18:
        log(f"cut: {args.docs} docs instead of 2^18")
    t0 = time.perf_counter()
    corpus = clueweb_corpus(args.seed, cfg.doc_len)
    batches = make_batches(corpus, 0, args.docs, cfg.docs_per_shard)
    queries = make_queries(batches, args.queries, args.seed + 1)
    log(f"set-up: corpus generated in {time.perf_counter() - t0:.1f} s")

    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        ix, dt = ingest(cfg, batches, root, 0)
        log(f"ingest: indexed and committed in {dt:.1f} s "
            f"({args.docs / dt:.0f} docs/s, smoke figure)")
        searcher = recover(root, "no tombstones")
        if any(r.live is not None for r in searcher.readers):
            raise AssertionError("a segment carries tombstones before any "
                                 "delete")
        serve_and_check(searcher, queries[:2 * SLOTS], set(),
                        "midgrid kernel")
        pallas_evidence(searcher, midgrid=True)
        del searcher
        gc.collect()

        deleted, dt = delete_and_commit(ix, 0, args.docs, args.seed)
        log(f"delete: {len(deleted)} docs tombstoned and committed in "
            f"{dt:.1f} s")
        searcher = recover(root, "tombstones")
        serve_and_check(searcher, queries, deleted, "masked skip kernel")
        pallas_evidence(searcher, midgrid=False)
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ---------------------------------------------------------------------------
# four chips: the sharded fleet
# ---------------------------------------------------------------------------

def run_four_chips(args, clock, devs) -> None:
    import jax
    import numpy as np
    from jax.sharding import AxisType
    from repro.configs.lucene_envelope import CONFIG as cfg
    from repro.core.searcher import ReaderCache, _index_arrays
    from repro.replication import FleetSearcher, ReplicaSyncer, ShardSpec
    from repro.serving.query_scheduler import QueryRequest, QueryScheduler
    from repro.storage import FSDirectory, open_latest

    n_shards = 4
    per_shard = args.docs // n_shards
    spec = ShardSpec(n_shards, "range", SHARD_RANGE)
    log(f"config: {cfg.name}; corpus ClueWeb09b-shaped, {n_shards} range "
        f"shards x {per_shard} docs, seed {args.seed}")
    if args.docs < 1 << 18:
        log(f"cut: {args.docs} docs in all instead of 2^18")
    corpus = clueweb_corpus(args.seed, cfg.doc_len)
    n_batches = -(-per_shard // cfg.docs_per_shard)
    root = tempfile.mkdtemp(prefix="chip_smoke_fleet_")
    try:
        deleted, writers = set(), []
        for si in range(n_shards):
            batches = make_batches(corpus, si * n_batches, per_shard,
                                   cfg.docs_per_shard)
            if si == 0:
                queries = make_queries(batches, args.queries, args.seed + 1)
            wdir = os.path.join(root, f"shard{si}", "writer")
            ix, dt = ingest(cfg, batches, wdir, si * SHARD_RANGE)
            gone, dt_del = delete_and_commit(ix, si * SHARD_RANGE, per_shard,
                                             args.seed + si)
            dt += dt_del
            assert set(spec.shard_of(list(gone))) == {si}
            deleted |= gone
            writers.append(wdir)
            log(f"shard {si}: indexed {per_shard} docs, deleted {len(gone)}, "
                f"committed in {dt:.1f} s")

        groups = []
        for si in range(n_shards):
            syncer = ReplicaSyncer(
                FSDirectory(os.path.join(root, f"shard{si}", "replica")),
                FSDirectory(writers[si]), replica_id=f"shard{si}",
                device=devs[si])
            syncer.sync_once()
            groups.append([syncer])
        for si, (syncer,) in enumerate(groups):
            arrs = [a for r in syncer.searcher.readers
                    for a in _index_arrays(r.index) + (r.doc_map, r.live)
                    if a is not None]
            placed = set().union(*(a.devices() for a in arrs))
            log(f"placement: shard {si} index arrays on {sorted(map(str, placed))}"
                f" ({index_shape(syncer.searcher)})")
            if placed != {devs[si]} or not all(a.committed for a in arrs):
                raise AssertionError(f"shard {si} is not resident on its own "
                                     f"chip {devs[si]}")

        mesh = jax.make_mesh((n_shards,), ("shard",), devices=devs[:n_shards],
                             axis_types=(AxisType.Auto,))
        fleet = FleetSearcher(groups, mesh=mesh)
        host = FleetSearcher(groups)
        sched = QueryScheduler(searcher=fleet, slots=SLOTS,
                               max_terms=QUERY_TERMS, k=K)
        reqs = [QueryRequest(rid=i, terms=q, k=K)
                for i, q in enumerate(queries)]
        for r in reqs:
            sched.submit(r)
        t0 = time.perf_counter()
        done = sched.run_to_completion()
        dt = time.perf_counter() - t0
        log(f"fleet serving: {len(done)} queries over the {n_shards}-device "
            f"mesh merge in {dt:.2f} s (smoke figure, compiles included); "
            f"{fleet.report()['shards_skipped']} shard visits skipped")

        segs = []
        for w in writers:
            segs.extend(open_latest(FSDirectory(w))[1])
        union = ReaderCache(prune=False, device=devs[0]).refresh(segs)
        q = np.stack([r.terms for r in done])
        fv = np.stack([r.scores for r in done])
        fi = np.stack([r.doc_ids for r in done])
        hv, hi = zip(*(map(np.asarray, host.search_batched(q[i:i + SLOTS], K))
                       for i in range(0, len(q), SLOTS)))
        if not (np.array_equal(np.concatenate(hv), fv)
                and np.array_equal(np.concatenate(hi), fi)):
            raise AssertionError("mesh merge differs from the host merge")
        log("fleet check: the 4-device mesh merge equals the host merge bit "
            "for bit (values and ids)")
        ov, oi = zip(*(map(np.asarray, union.search_batched(q[i:i + SLOTS], K))
                       for i in range(0, len(q), SLOTS)))
        ov, oi = np.concatenate(ov), np.concatenate(oi)
        exact = bool(np.array_equal(ov, fv))
        if not np.allclose(fv, ov, rtol=RTOL, atol=0.0):
            raise AssertionError(f"fleet scores differ from the union oracle "
                                 f"(max abs diff {np.abs(fv - ov).max()})")
        served = set(fi[fi >= 0].tolist())
        if served & deleted:
            raise AssertionError("a deleted doc was served by the fleet")
        log(f"fleet check: top-{K} scores equal the exhaustive union-index "
            f"oracle ({'bit for bit' if exact else f'within rtol {RTOL}'}) "
            f"on {len(done)} queries; no deleted doc served")
        for si, d in enumerate(devs[:n_shards]):
            mem = d.memory_stats() or {}
            log(f"device memory: chip {si} bytes_in_use "
                f"{mem.get('bytes_in_use')}")
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--docs", type=int, default=None,
                    help="documents indexed in all, split over the shards "
                         "with --chips 4 (default 2^18 on one chip, 2^17 "
                         "on four)")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.docs is None:
        args.docs = 1 << 18 if args.chips == 1 else 1 << 17
    devs = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    t0 = time.perf_counter()
    log(f"device: {devs[0].device_kind} x {len(devs)}; compile cache "
        f"{cache_dir}")
    if args.chips == 4:
        run_four_chips(args, clock, devs)
    else:
        run_one_chip(args, clock)
    log(f"compile: {clock.line()}")
    log(f"wall: {time.perf_counter() - t0:.1f} s (smoke figure)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
